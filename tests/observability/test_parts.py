"""``obs/parts.py``: the rule that reads a compiled program's
``op_name``s back into part, pass, opcode and phase, on canned HLO
lines (the forms jax 0.9.0 and XLA write), and the operator's reader
on a laid-out trace."""

import io
import json
import os

import pytest

from realhf_tpu.obs import parts

P = "jit(train_step)/jit(main)"
FB = f"{P}/while/body/forward_backward"


def line(name, opcode="fusion", op_name=None, shape="bf16[4096,896]{1,0}",
         extra=""):
    meta = f', metadata={{op_name="{op_name}" source_file="x.py"}}' \
        if op_name is not None else ""
    return f"  %{name} = {shape} {opcode}(%a, %b){extra}{meta}"


@pytest.mark.parametrize("op_name,want", [
    # forward, under the scan of the microbatches and the layers' scan
    (f"{FB}/jvp()/while/body/closed_call/attn_proj/dot_general",
     ("attn_proj", "fwd", "forward_backward")),
    # a scope entered directly under the jvp carries the wrapper
    (f"{FB}/jvp(loss)/reduce_sum", ("loss", "fwd", "forward_backward")),
    # the backward: a transpose( anywhere in the path
    (f"{FB}/transpose(jvp())/while/body/closed_call/checkpoint/mlp/mul",
     ("mlp", "bwd", "forward_backward")),
    (f"{FB}/transpose(jvp(attn))/transpose",
     ("attn", "bwd", "forward_backward")),
    # a rematerialised forward lies in the backward's loop and is no
    # backward
    (f"{FB}/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/custom_call",
     ("attn", "remat", "forward_backward")),
    # the innermost part wins: the head inside the loss, the shared
    # expert inside the experts
    (f"{FB}/jvp(loss)/vocab_head/while/body/checkpoint/vocab_head/"
     "dot_general", ("vocab_head", "fwd", "forward_backward")),
    (f"{FB}/jvp()/experts/shared_expert/dot_general",
     ("shared_expert", "fwd", "forward_backward")),
    # the head of a weighted sum (ops/functional.py:
    # weighted_logprob_sum): its forward rule makes the logits (fwd)
    # and runs the two gradient products, scope "gradient" (bwd); its
    # backward rule's scalings are traced under the call's scope
    (f"{FB}/jvp(loss)/vocab_head/while/body/closed_call/slh,hv->slv/"
     "dot_general", ("vocab_head", "fwd", "forward_backward")),
    (f"{FB}/jvp(loss)/vocab_head/while/body/closed_call/gradient/"
     "dot_general", ("vocab_head", "bwd", "forward_backward")),
    (f"{FB}/transpose(jvp(loss))/vocab_head/mul",
     ("vocab_head", "bwd", "forward_backward")),
    # (a primitive of a like name is no scope)
    (f"{FB}/jvp(loss)/vocab_head/stop_gradient",
     ("vocab_head", "fwd", "forward_backward")),
    # the experts' steps
    (f"{FB}/jvp()/experts/route/top_k",
     ("experts/route", "fwd", "forward_backward")),
    (f"{FB}/transpose(jvp())/experts/combine/scatter-add",
     ("experts/combine", "bwd", "forward_backward")),
    (f"{FB}/jvp()/experts/mul", ("experts", "fwd", "forward_backward")),
    # the grouped products as the repo's own kernels: a Pallas call
    # keeps its op_name, so the pass is known (the compiler's
    # ragged-dot kernel loses it: COMPILER_MADE, below)
    (f"{FB}/jvp(layers)/experts/cond/branch_1_fun/products/gmm/pallas_call",
     ("experts/products", "fwd", "forward_backward")),
    (f"{FB}/transpose(jvp(layers))/jvp(layers)/checkpoint/"
     "rematted_computation/experts/cond/branch_1_fun/products/gmm/"
     "pallas_call", ("experts/products", "remat", "forward_backward")),
    (f"{FB}/transpose(jvp(layers))/jvp(layers)/checkpoint/experts/cond/"
     "branch_1_fun/products/gmm_t/pallas_call",
     ("experts/products", "bwd", "forward_backward")),
    (f"{FB}/transpose(jvp(layers))/jvp(layers)/checkpoint/experts/cond/"
     "branch_0_fun/while/body/closed_call/checkpoint/products/tgmm/"
     "pallas_call", ("experts/products", "bwd", "forward_backward")),
    # (the slow branch's chunk, rematerialised inside the backward)
    (f"{FB}/transpose(jvp(layers))/jvp(layers)/checkpoint/experts/cond/"
     "branch_0_fun/while/body/closed_call/checkpoint/"
     "rematted_computation/products/gmm/pallas_call",
     ("experts/products", "remat", "forward_backward")),
    # no part: what only the phase holds, and what nothing holds
    (f"{FB}/transpose(jvp())/while/body/closed_call/remat2",
     (None, "bwd", "forward_backward")),
    (f"{P}/while/body/grad_accum/add", ("grad_accum", "fwd", None)),
    (f"{P}/optimizer/mul", ("optimizer", "fwd", None)),
    (f"{P}/convert_element_type", (None, "fwd", None)),
    ("", (None, "fwd", None)),
    # generation: the model's parts nest in the program's phases
    ("jit(generate)/jit(main)/while/body/decode/attn/custom_call",
     ("attn", "fwd", "decode")),
    ("jit(generate)/jit(main)/while/body/decode/vocab_head/dot_general",
     ("vocab_head", "fwd", "decode")),
    ("jit(generate)/jit(main)/prefill/while/body/attn_proj/dot_general",
     ("attn_proj", "fwd", "prefill")),
    ("jit(generate)/jit(main)/while/body/sample/sort", (None, "fwd",
                                                        "sample")),
    # a function of the same name is no scope
    ("jit(decode)/jit(main)/attnx/mul", (None, "fwd", None)),
])
def test_classify(op_name, want):
    assert parts.classify(op_name) == want


@pytest.mark.parametrize("text,want", [
    (line("fusion.5"), "fusion"),
    ("  ROOT %t = (s32[], bf16[8,128]{1,0:T(8,128)(2,1)}) while(%x), "
     "condition=%c, body=%b", "while"),
    ("  %ar = f32[8]{0} all-reduce-start(%x), to_apply=%add", 
     "all-reduce-start"),
    ("  %c.1 = bf16[2,8]{1,0:T(2,128)(2,1)S(1)} copy(%x)", "copy"),
    ("  %k = bf16[8]{0} custom-call(%q), custom_call_target=\"tpu_custom_call\"",
     "custom-call"),
])
def test_opcode(text, want):
    assert parts.opcode_of(text) == want


PROGRAM = "\n".join([
    "HloModule jit_train_step, is_scheduled=true, entry_computation_layout={()}",
    "",
    "%fused_computation.1 (p0: bf16[8]) -> bf16[8] {",
    line("inner.1", "multiply", f"{FB}/jvp()/mlp/mul"),
    "  ROOT " + line("inner.2", "add", f"{FB}/jvp()/attn/add").strip(),
    "}",
    "",
    "%region_1.2 (x: f32[], y: f32[]) -> f32[] {",
    "  ROOT " + line("add.9", "add", "vocab_head/reduce_sum",
                     shape="f32[]").strip(),
    "}",
    "",
    "%region_b.5 (arg: (bf16[8])) -> (bf16[8]) {",
    # jax fills a conditional's unused residuals with zeros and names
    # them for nothing: they feed the conditional that returns them
    line("broadcast.4", "broadcast", f"{P}/while/body/closed_call"),
    "  ROOT %tuple.5 = (bf16[8]{0}) tuple(%broadcast.4)",
    "}",
    "",
    "%body.3 (arg: (s32[], bf16[8])) -> (s32[], bf16[8]) {",
    line("p.1", "parameter"),
    line("cond.6", "conditional", f"{FB}/jvp()/experts/cond",
         extra=", branch_computations={%region_a.4, %region_b.5}"),
    # the compiler's own kernel under the compiler's own op_name
    line("ragged-dot-none.2", "custom-call", "ragged-dot-none"),
    # a fusion the compiler cloned and left no op_name: what is inside
    line("fusion.9", "fusion",
         extra=", kind=kLoop, calls=%fused_computation.1"),
    # a copy into another memory space feeds the product that reads it
    line("copy-start.3", "copy-start"),
    "  %copy-done.3 = bf16[8]{0:S(1)} copy-done(%copy-start.3)",
    line("gte.1", "get-tuple-element", f"{FB}/jvp()/while"),
    # a fusion takes the op_name of its calling line
    "  %fusion.7 = bf16[8]{0} fusion(%gte.1, %copy-done.3), "
    "kind=kOutput, calls=%fused_computation.1, "
    f'metadata={{op_name="{FB}/jvp()/attn_proj/dot_general"}}',
    line("flash_fwd.3", "custom-call", f"{FB}/jvp()/attn/pallas_call"),
    line("reduce.4", "reduce", f"{FB}/jvp(loss)/vocab_head/reduce_sum",
         extra=", dimensions={1}, to_apply=%region_1.2"),
    line("rs-start.1", "reduce-scatter-start",
         f"{FB}/transpose(jvp(loss))/vocab_head/dot_general"),
    line("rs-done.1", "reduce-scatter-done",
         f"{FB}/transpose(jvp(loss))/vocab_head/dot_general"),
    line("copy.5", "copy"),
    # a copy out of fast memory into the loop's carry: its one user is
    # the body's tuple, so it takes the part of what it was made FROM
    # (the head's dW, which a chunk's fusion leaves in fast memory)
    line("fusion.11", "fusion", f"{FB}/jvp(loss)/vocab_head/while/body/"
         "gradient/dot_general",
         extra=", kind=kOutput, calls=%fused_computation.1"),
    "  %copy-start.8 = (bf16[8]{0}, bf16[8]{0:S(1)}, u32[]) "
    "copy-start(%fusion.11)",
    "  %copy-done.8 = bf16[8]{0} copy-done(%copy-start.8)",
    "  ROOT %tuple.2 = (s32[], bf16[8]{0}) tuple(%p.1, %copy-done.8)",
    "}",
    "",
    "ENTRY %main.9 (a: bf16[8]) -> bf16[8] {",
    line("a", "parameter"),
    # in a phase and in no part: NOT put down to what it feeds
    "  %sort.1 = s32[8]{0} sort(%a), dimensions={0}, to_apply=%region_1.2, "
    'metadata={op_name="jit(generate)/jit(main)/while/body/sample/sort"}',
    "  %gather.1 = bf16[8]{0} gather(%a, %sort.1), "
    'metadata={op_name="jit(generate)/jit(main)/while/body/decode/embed/'
    'gather"}',
    line("while.1", "while", f"{P}/while", shape="(s32[], bf16[8]{0})",
         extra=", condition=%cond.2, body=%body.3"),
    "  ROOT " + line("fusion.8", "fusion", f"{P}/optimizer/add",
                     extra=", kind=kLoop, calls=%fused_computation.2"
                     ).strip(),
    "}",
])


def test_parse_program_keeps_what_the_device_runs():
    ops = parts.parse_program(PROGRAM)
    # a while body's operations by their own names; nothing of a fused
    # computation, of an applied scalar function, or of the plumbing
    assert sorted(ops) == [
        "broadcast.4", "cond.6", "copy-done.3", "copy-done.8",
        "copy-start.3", "copy-start.8", "copy.5", "flash_fwd.3",
        "fusion.11", "fusion.7", "fusion.8", "fusion.9", "gather.1",
        "ragged-dot-none.2", "reduce.4", "rs-done.1", "rs-start.1",
        "sort.1", "while.1"]
    assert ops["fusion.7"][:4] == ("attn_proj", "fwd", "fusion",
                                   "forward_backward")
    assert ops["flash_fwd.3"][:4] == ("attn", "fwd", "custom-call",
                                      "forward_backward")
    assert ops["reduce.4"][:3] == ("vocab_head", "fwd", "reduce")
    assert ops["rs-start.1"][:3] == ("vocab_head", "bwd",
                                     "reduce-scatter-start")
    assert ops["rs-done.1"][2] in parts.COLLECTIVES
    assert ops["copy.5"] == (None, "fwd", "copy", None, "")
    assert ops["fusion.8"][:4] == ("optimizer", "fwd", "fusion", None)
    assert ops["fusion.7"][4] == "attn_proj/dot_general"
    # what the compiler made: its own op_name, a cloned fusion's
    # inside, and what was made to feed another operation
    assert ops["ragged-dot-none.2"][:3] == ("experts/products", "?",
                                            "custom-call")
    assert ops["fusion.9"][:2] == ("attn", "fwd")
    assert ops["copy-done.3"][:4] == ("attn_proj", "fwd", "copy-done",
                                      "forward_backward")
    assert ops["copy-start.3"][0] == "attn_proj"  # two steps away
    assert ops["fusion.11"][:2] == ("vocab_head", "bwd")
    assert ops["copy-done.8"][:4] == ("vocab_head", "fwd", "copy-done",
                                      "forward_backward")
    assert ops["copy-start.8"][0] == "vocab_head"
    assert ops["broadcast.4"][:4] == ("experts", "fwd", "broadcast",
                                      "forward_backward")
    assert ops["cond.6"][:3] == ("experts", "fwd", "conditional")
    assert ops["sort.1"][:4] == (None, "fwd", "sort", "sample")
    assert ops["gather.1"][:4] == ("embed", "fwd", "gather", "decode")


def test_grouped_products_by_kernel_and_by_the_compilers_own():
    """The repo's kernels land in ``experts/products`` WITH their pass;
    the engine's count of them (``ops.moe.grouped_product_calls``: the
    attributes ``moe_products``, ``moe_gmm_calls``,
    ``moe_ragged_dot_calls`` of a sparse model's ``engine:*`` spans)
    reads the custom calls outside fusion bodies by name."""
    from realhf_tpu.ops import moe as moe_ops

    products = f"{FB}/jvp(layers)/experts/products"
    back = (f"{FB}/transpose(jvp(layers))/jvp(layers)/checkpoint/experts/"
            "products")
    kernels = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        "",
        "ENTRY %main.9 (a: bf16[8]) -> bf16[8] {",
        line("a", "parameter"),
        line("jvp_gmm_.1", "custom-call", f"{products}/gmm/pallas_call"),
        line("gmm.7", "custom-call", f"{back.replace('/experts', '/rematted_computation/experts')}/gmm/pallas_call"),
        line("transpose_jvp_gmm_t__.1", "custom-call",
             f"{back}/gmm_t/pallas_call"),
        line("transpose_jvp_tgmm__.1", "custom-call",
             f"{back}/tgmm/pallas_call"),
        line("flash_fwd.3", "custom-call", f"{FB}/jvp()/attn/pallas_call"),
        line("fusion.1", "fusion", f"{products}/jit(_where)/select_n"),
        "  ROOT " + line("tuple.2", "tuple").strip(),
        "}",
    ])
    ops = parts.parse_program(kernels)
    assert {name: ops[name][:3] for name in ops if "gmm" in name} == {
        "jvp_gmm_.1": ("experts/products", "fwd", "custom-call"),
        "gmm.7": ("experts/products", "remat", "custom-call"),
        "transpose_jvp_gmm_t__.1": ("experts/products", "bwd",
                                    "custom-call"),
        "transpose_jvp_tgmm__.1": ("experts/products", "bwd",
                                   "custom-call")}
    assert ops["fusion.1"][:2] == ("experts/products", "fwd")
    assert moe_ops.grouped_product_calls(kernels) == dict(
        moe_products="gmm", moe_gmm_calls=4, moe_ragged_dot_calls=0)
    # the path that keeps lax.ragged_dot: the compiler's kernel, its
    # pass unknown; and a program with neither (XLA:CPU)
    assert moe_ops.grouped_product_calls(PROGRAM) == dict(
        moe_products="ragged_dot", moe_gmm_calls=0,
        moe_ragged_dot_calls=1)
    assert moe_ops.grouped_product_calls(
        kernels.replace("gmm", "dot"))["moe_products"] == "ragged_dot"


class _Compiled:
    def __init__(self, text, stats=None):
        self.text, self.stats = text, stats

    def as_text(self):
        return self.text

    def memory_analysis(self):
        return self.stats


REMAT = (f"{FB}/transpose(jvp())/while/body/closed_call/checkpoint/"
         "rematted_computation")


def _layer(remat_parts):
    """One layer's products as a compiled text holds them: four of the
    attention projections forward and eight backward, and one in the
    rematerialised forward for each of ``remat_parts``, the first of
    them inside a fusion body."""
    fwd = [line(f"convolution.{i}", "convolution",
                f"{FB}/jvp()/while/body/closed_call/attn_proj/dot_general")
           for i in range(4)]
    bwd = [line(f"dot.{i}", "dot",
                f"{FB}/transpose(jvp())/while/body/closed_call/checkpoint/"
                "attn_proj/dot_general") for i in range(8)]
    again = [line(f"convolution.{9 + i}", "convolution",
                  f"{REMAT}/{part}/dot_general")
             for i, part in enumerate(remat_parts)]
    # what is no product does not count, whatever its scope
    other = [line("fusion.77", "fusion", f"{REMAT}/attn_proj/mul",
                  extra=", kind=kLoop, calls=%fused_computation.1"),
             line("flash_fwd.3", "custom-call", f"{REMAT}/attn/custom_call")]
    body = "%fused_computation.1 (p: bf16[8]) -> bf16[8] {\n" \
        + "\n".join(again[:1]) + "\n}\n\n"
    return body + "%body (x: bf16[8]) -> bf16[8] {\n" + "\n".join(
        fwd + bwd + again[1:] + other) + "\n}\n"


@pytest.mark.parametrize("remat_parts,want", [
    # every projection runs a second time: 4 a layer (one of them
    # inside a fusion body: counted)
    (["attn_proj"] * 4, 4),
    # the block keeps what the projections made
    ([], 0),
    # a product under ANOTHER part in the rematerialised forward (the
    # feed-forward's, the router's, the head's) is not counted
    (["mlp", "mlp", "experts/route", "vocab_head"], 0),
], ids=["recomputed", "kept", "another_part"])
def test_count_products_by_part_and_pass(remat_parts, want):
    text = "HloModule jit_train_step, is_scheduled=true\n\n" \
        + _layer(remat_parts)
    assert parts.count_products(text, "attn_proj", "remat") == want
    assert parts.count_products(text, "attn_proj", "fwd") == 4
    assert parts.count_products(text, "attn_proj", "bwd") == 8
    assert parts.count_products(text, "mlp", "remat") \
        == remat_parts.count("mlp")
    # an unrolled stack of three layers holds them three times
    assert parts.count_products(text * 3, "attn_proj", "remat") == 3 * want


def test_read_program_facts():
    class Stats:
        argument_size_in_bytes, temp_size_in_bytes = 7, 5
        output_size_in_bytes = alias_size_in_bytes = 3
        generated_code_size_in_bytes = 0
    facts = parts.read_program(
        _Compiled(PROGRAM, Stats),
        derive=lambda text: dict(kernels=text.count("flash_fwd")))
    assert facts.module == "jit_train_step"
    assert facts.attributes == dict(kernels=1)
    assert facts.memory["argument_size_in_bytes"] == 7
    assert set(facts.memory) == set(parts.MEMORY_FIELDS)
    other = parts.read_program(_Compiled(PROGRAM + "\n"))
    assert other.fingerprint != facts.fingerprint  # of the text
    assert other.memory["temp_size_in_bytes"] == 0  # no count given
    again = json.loads(json.dumps(facts.as_dict()))
    assert again["ops"]["fusion.7"][:2] == ["attn_proj", "fwd"]
    assert again["fingerprint"] == facts.fingerprint


def test_own_seconds_take_a_loop_off_its_body():
    events = [("while", 0.0, 10.0), ("a", 1.0, 4.0), ("b", 4.0, 6.0),
              ("a", 6.0, 7.0), ("tail", 10.0, 11.0)]
    assert parts.self_seconds(events) == {
        "while": pytest.approx(4.0), "a": pytest.approx(4.0),
        "b": pytest.approx(2.0), "tail": pytest.approx(1.0)}


def laid_profile(tmp_path, chips=2):
    """A profile directory with ``programs.json`` and, in place of the
    ``.xplane.pb``'s device lines, ``PROGRAM``'s operations laid out a
    chip: the loop spans its body, one operation is in no text."""
    d = tmp_path / "plugins" / "profile" / "now"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(b"")
    facts = parts.read_program(_Compiled(PROGRAM)).as_dict()
    facts["memory"].update(argument_size_in_bytes=6_865_000_000,
                           temp_size_in_bytes=6_464_000_000)
    (d / parts.PROGRAMS_FILE).write_text(json.dumps({"f00d": facts}))
    body = ["fusion.7", "flash_fwd.3", "reduce.4", "rs-start.1",
            "rs-done.1", "copy.5", "stranger.1"]  # not all of them
    ops = [("%while.1 = (s32[]) while(%t), body=%body.3", 1.0, 9.0)]
    ops += [(f"%{n} = bf16[8]{{0}} op(%x)", 1.0 + i, 2.0 + i)
            for i, n in enumerate(body)]
    ops += [("%fusion.8 = bf16[8]{0} fusion(%w)", 9.0, 9.5)]
    return {chip: dict(ops=ops, modules=[("jit_train_step(3)", 0.5, 10.0)])
            for chip in range(chips)}


def test_table_adds_up_to_the_programs_busy_seconds(tmp_path, monkeypatch):
    devices = laid_profile(tmp_path)
    monkeypatch.setattr(parts, "read_device_lines", lambda path: devices)
    out = io.StringIO()
    parts.table(str(tmp_path), out=out)
    text = out.getvalue()
    head = text.splitlines()[0]
    assert head.startswith("== jit_train_step: 8.5000 s busy a chip "
                           "(2 chips, 9 operations)")
    rows = [ln.split() for ln in text.splitlines()
            if ln.strip().endswith((" fwd", " bwd", " remat", " ?"))]
    # the parts' rows add up to the program's busy seconds, a chip
    assert sum(float(r[0]) for r in rows) == pytest.approx(8.5)
    by = {(r[3], r[4], r[5]): float(r[0]) for r in rows}
    assert by[("forward_backward", "vocab_head", "bwd")] == 2.0
    assert by[("forward_backward", "attn", "fwd")] == 1.0
    assert by[("-", "optimizer", "fwd")] == 0.5
    # the loop's own second, the copy and the stranger: no part
    assert by[("-", "unscoped", "fwd")] + by[("-", "unscoped", "?")] == 3.0
    assert "flash_fwd.3 custom-call  [forward_backward/attn/fwd]  " \
        "attn/pallas_call" in text
    assert "arguments + temporaries 13.329 GB" in text


def test_reader_needs_a_capture_made_by_the_one_control(tmp_path):
    with pytest.raises(SystemExit, match="no .xplane.pb"):
        parts.table(str(tmp_path))
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    with pytest.raises(SystemExit, match="programs.json"):
        parts.table(str(tmp_path))
    assert parts.programs_path(str(tmp_path)) == os.path.join(
        str(tmp_path), "programs.json")


def test_the_reader_imports_no_benchmark_and_no_model():
    import subprocess
    import sys
    code = ("import sys; import realhf_tpu.obs.parts; "
            "bad = [m for m in sys.modules if m.startswith(('benchmark', "
            "'realhf_tpu.models', 'realhf_tpu.engine', 'jax'))]; "
            "print(bad); sys.exit(bool(bad))")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=os.path.dirname(os.path.dirname(
                              os.path.dirname(os.path.abspath(__file__)))))
    assert done.returncode == 0, done.stdout + done.stderr
