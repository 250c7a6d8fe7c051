"""``benchmark/trace_reduce.py`` on a constructed trace whose busy
union, gaps, own times and labels are known."""

import pytest

from benchmark import trace_reduce as tr

CATEGORIES = dict(actor_gen="gen", ref_inf="inf", actor_train="train")

# one device, seconds. A while op spans two fusions (nested); then a
# gap inside the gen MFC; a train op; a gap between the MFCs; a second
# step after a gap between steps.
OPS = [
    ("%while.1 = (s32[]) while((s32[]) %t), body=%b", 1.0, 3.0),
    ("%fusion.1 = bf16[8]{0:T(8)} fusion(bf16[8] %p)", 1.0, 1.5),
    ("%fusion.2 = bf16[8]{0:T(8)} fusion(bf16[8] %p)", 2.0, 3.0),
    ("%custom-call.7 = f32[4] custom-call(f32[4] %x)", 3.5, 4.0),
    ("%fusion.9 = bf16[8]{0} fusion(bf16[8] %p)", 5.0, 6.0),
    ("%fusion.1 = bf16[8]{0:T(8)} fusion(bf16[8] %p)", 8.0, 9.0),
]
MODULES = [("jit_run(123)", 1.0, 4.0), ("jit_step(456)", 5.0, 6.0),
           ("jit_run(123)", 8.0, 9.0)]
SPANS = [("bench:step", 1.0, 6.5), ("bench:mfc:actor_gen", 1.0, 4.2),
         ("mfc:actor_gen", 1.1, 4.1),
         ("bench:mfc:actor_train", 4.8, 6.2),
         ("bench:step", 7.5, 9.0), ("bench:mfc:actor_gen", 7.5, 9.0),
         ("bench:reshard", 7.5, 7.9)]
TRACE = dict(devices={0: dict(ops=OPS, modules=MODULES)}, spans=SPANS)


def test_busy_is_the_union_not_the_sum():
    window = (1.0, 9.0)
    assert tr.busy_seconds(OPS, window) == pytest.approx(2 + .5 + 1 + 1)
    assert sum(e - s for _, s, e in OPS) == pytest.approx(6.0)
    assert tr.merged(OPS) == [(1.0, 3.0), (3.5, 4.0), (5.0, 6.0),
                              (8.0, 9.0)]


def test_clip_to_the_window():
    assert tr.busy_seconds(OPS, (2.5, 5.5)) == pytest.approx(.5 + .5 + .5)


def test_idle_gaps():
    assert tr.idle_gaps(OPS, (1.0, 9.0)) == [(3.0, 3.5), (4.0, 5.0),
                                             (6.0, 8.0)]
    assert tr.idle_gaps(OPS, (0.0, 9.5))[0] == (0.0, 1.0)
    assert tr.idle_gaps(OPS, (0.0, 9.5))[-1] == (9.0, 9.5)


def test_own_time_takes_children_off_their_parent():
    own = tr.self_seconds(tr.with_module(OPS, MODULES))
    assert own["jit_run/while.1 while"] == pytest.approx(2.0 - 0.5 - 1.0)
    assert own["jit_run/fusion.1 fusion"] == pytest.approx(0.5 + 1.0)
    assert own["jit_run/fusion.2 fusion"] == pytest.approx(1.0)
    assert own["jit_step/fusion.9 fusion"] == pytest.approx(1.0)
    assert sum(own.values()) == pytest.approx(tr.busy_seconds(
        OPS, (0, 10)))


@pytest.mark.parametrize("name,short", [
    ("%fusion.3 = bf16[2,4]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[2] %x)",
     "fusion.3 fusion"),
    ("%copy-start.1 = (bf16[2]{0:T(8)}, u32[]{:S(2)}) copy-start(%x)",
     "copy-start.1 copy-start"),
    ("plain", "plain")])
def test_short_op(name, short):
    assert tr.short_op(name) == short


@pytest.mark.parametrize("t,label", [
    (3.2, "gen"), (4.5, "between-mfcs"), (5.5, "train"),
    (7.0, "between-steps"), (7.7, "reshard"), (8.5, "gen"),
    (0.5, "between-steps"), (99.0, "between-steps")])
def test_labels(t, label):
    assert tr.label_of(t, SPANS, CATEGORIES) == label
    assert tr.labeller(SPANS, CATEGORIES)(t) == label


def test_an_mfc_the_kind_does_not_name_keeps_its_name():
    spans = [("bench:step", 0, 2), ("mfc:critic_inf", 0, 1)]
    assert tr.label_of(0.5, spans, CATEGORIES) == "mfc:critic_inf"


def test_reduce():
    out = tr.reduce(TRACE, CATEGORIES)
    assert out["window_s"] == pytest.approx(8.0)   # 1.0 .. 9.0
    assert out["busy_s"] == pytest.approx(4.5)
    assert out["idle_share"] == pytest.approx(1 - 4.5 / 8.0)
    assert out["idle_by_label"] == pytest.approx(
        {"gen": 0.5, "between-mfcs": 1.0, "between-steps": 2.0})
    ops = dict(map(tuple, out["breakdown"]["device_ops"]))
    assert ops["jit_run/fusion.1 fusion"] == pytest.approx(1.5)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0] == ["sum:between-steps", pytest.approx(2.0)]
    assert ["gap:between-steps", pytest.approx(2.0)] in gaps
    assert len(out["breakdown"]["device_ops"]) <= 10 and len(gaps) <= 10


def test_reduce_averages_over_the_chips_used():
    two = dict(devices={0: dict(ops=OPS, modules=MODULES),
                        1: dict(ops=OPS[3:], modules=MODULES)},
               spans=SPANS)
    out = tr.reduce(two, CATEGORIES, chips=2)
    assert out["busy_s_per_chip"] == pytest.approx([4.5, 2.5])
    assert out["busy_s"] == pytest.approx(3.5)
    assert tr.reduce(two, CATEGORIES, chips=1)["busy_s"] \
        == pytest.approx(4.5)


def test_nothing_to_read_gives_nothing():
    assert tr.reduce(dict(devices={}, spans=SPANS), CATEGORIES) is None
    assert tr.reduce(dict(devices=TRACE["devices"], spans=[]),
                     CATEGORIES) is None


def test_read_a_recorded_trace(tmp_path):
    """A trace recorded here (CPU: host spans, no device plane) reads
    back with the harness's spans on it."""
    import glob

    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:step"):
        with jax.profiler.TraceAnnotation("bench:mfc:actor_gen"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    trace = tr.read_xplane(path)
    names = [n for n, _, _ in trace["spans"]]
    assert "bench:step" in names and "bench:mfc:actor_gen" in names
    step = next(x for x in trace["spans"] if x[0] == "bench:step")
    assert 0 < step[2] - step[1] < 60
    assert tr.reduce(trace, CATEGORIES) is None  # no device ran
