"""The blocked clock of the traced run: an MFC's span ends when what
the interface RETURNED is ready, not when it was enqueued. Generation
and the inference forwards change no weights, so blocking on the
weights alone would time their enqueue once an interface stops pulling
its outputs to the host."""

import time

import pytest

from benchmark import observe


class Unfinished:
    """Stands for a device array whose program is still running: ready
    only some time after ``block_until_ready`` is asked for."""

    def __init__(self, secs=0.05):
        self.secs = secs
        self.ready_at = None

    def block_until_ready(self):
        time.sleep(self.secs)
        self.ready_at = time.monotonic()
        return self


class Sample:
    """As ``SequenceSample``: not a pytree, its arrays under ``data``."""

    def __init__(self, **data):
        self.data = data


class Host:
    models, replicas = {}, {}


@pytest.fixture
def fake_interface(monkeypatch):
    """``ModelHost.execute`` replaced, under the observer's wrapper, by
    an interface that returns at once what the test hands it."""
    from realhf_tpu.system.model_host import ModelHost
    monkeypatch.setattr(ModelHost, "execute",
                        lambda host, node_name, inp: inp)
    return ModelHost


@pytest.mark.parametrize("wrap", [
    lambda leaf: Sample(packed_logprobs=leaf, nothing=None),
    lambda leaf: dict(loss=leaf, n_tokens=7),
], ids=["sample", "stats"])
@pytest.mark.parametrize("traced", [True, False])
def test_mfc_span_ends_when_its_outputs_are_ready(fake_interface, wrap,
                                                  traced, tmp_path):
    leaf = Unfinished()
    obs = observe.Observer(1.0, watch=None,
                           trace_dir=str(tmp_path) if traced else None)
    obs.install()
    try:
        out = fake_interface.execute(Host(), "ref_inf", wrap(leaf))
    finally:
        obs.uninstall()
    assert fake_interface.execute(Host(), "ref_inf", 3) == 3  # unwrapped
    (step, name, start, end), = obs.mfcs
    assert (step, name) == (0, "ref_inf")
    assert getattr(out, "data", out) is not None
    if traced:
        assert leaf.ready_at is not None and end >= leaf.ready_at
        assert end - start >= leaf.secs
    else:  # the end-to-end run keeps the program's own overlap
        assert leaf.ready_at is None and end - start < leaf.secs


def test_a_sample_without_data_blocks_on_nothing():
    import jax
    sample = Sample()
    sample.data = None  # a metadata-only view
    returned = observe.Observer._returned(sample)
    assert jax.tree_util.tree_leaves(returned) == []
    jax.block_until_ready((returned, []))
