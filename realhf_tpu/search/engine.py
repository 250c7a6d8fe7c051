"""Allocation search: C++ MCMC over per-MFC placements.

TPU-native counterpart of the reference search engine
(``realhf/search_engine/search.py:25`` driving the C++
``mdm_search.multi_mcmc_search``, csrc/search/search.cpp): Python
enumerates candidate placements per MFC -- a contiguous chip slice and
a (dp, tp) layout that fits HBM -- and prices each with an analytic
TPU cost model (MXU flops at an efficiency factor for compute-bound
phases, HBM bandwidth for decode, ICI bandwidth for parameter
reallocation between layouts). The native module
(``csrc/mcmc_search.cpp``) then runs simulated annealing, scoring
assignments by simulating the dataflow graph (dependency + device
contention scheduling, same-role realloc charges), and returns the
best assignment.

The .so is compiled on first use with g++ (no pybind11 in the image;
plain C ABI + ctypes).
"""

import ctypes
import dataclasses
import os
import subprocess
from typing import Dict, List, Optional, Tuple

import numpy as np

from realhf_tpu.api.config import ModelInterfaceType
from realhf_tpu.base import logging
from realhf_tpu.models.operators import n_params
from realhf_tpu.parallel.mesh import ParallelismConfig

logger = logging.getLogger("search", "benchmark")

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc",
    "mcmc_search.cpp")


# ---------------------------------------------------------------------
# Native module loading (compile on demand)
# ---------------------------------------------------------------------
_lib = None


def _build_dir() -> str:
    d = os.path.join(os.path.dirname(_CSRC), "build")
    os.makedirs(d, exist_ok=True)
    return d


def load_native():
    """Compile (if stale) and load the MCMC search shared object."""
    global _lib
    if _lib is not None:
        return _lib
    # cache key = source content hash (mtime is meaningless after a
    # fresh clone, and the .so is never committed -- platform-specific)
    import hashlib
    with open(_CSRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_build_dir(), f"libmcmc_search-{digest}.so")
    if not os.path.exists(so):
        import glob
        for stale in glob.glob(
                os.path.join(_build_dir(), "libmcmc_search*.so")):
            try:
                os.remove(stale)
            except OSError:
                pass
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
               _CSRC, "-o", so]
        logger.info("Building native search module: %s", " ".join(cmd))
        try:
            subprocess.run(cmd, check=True, capture_output=True,
                           text=True)
        except subprocess.CalledProcessError as e:
            logger.error("Native search build failed:\n%s", e.stderr)
            raise
    lib = ctypes.CDLL(so)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i8p = ctypes.POINTER(ctypes.c_int8)
    f64p = ctypes.POINTER(ctypes.c_double)
    lib.mcmc_search.restype = ctypes.c_double
    lib.mcmc_search.argtypes = [
        ctypes.c_int, ctypes.c_int, i64p, i32p, i32p, f64p, i32p, i32p,
        i8p, f64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_double,
        ctypes.c_double, ctypes.c_uint64, i64p]
    lib.simulate_assignment.restype = ctypes.c_double
    lib.simulate_assignment.argtypes = [
        ctypes.c_int, ctypes.c_int, i64p, i32p, i32p, f64p, i32p, i32p,
        i8p, f64p, ctypes.c_int64, i64p]
    _lib = lib
    return lib


# ---------------------------------------------------------------------
# Cost model (v5e defaults; overridable)
# ---------------------------------------------------------------------
@dataclasses.dataclass
class TPUCostModel:
    peak_flops: float = 197e12        # bf16 per chip
    mxu_efficiency: float = 0.4       # achieved fraction on train/prefill
    hbm_bandwidth: float = 819e9      # bytes/s per chip
    ici_bandwidth: float = 186e9      # bytes/s per chip (all links)
    hbm_budget: float = 16e9 * 0.6


#: calibration artifact the measured-fit entry (scripts/calibrate.py)
#: persists and default_cost_model() picks up
CALIBRATION_ENV = "REALHF_TPU_CALIBRATION"
CALIBRATION_FILE = "calibration_tpu.json"
_calib_logged: set = set()


def load_cost_model(path: str) -> Optional[TPUCostModel]:
    """Parse a calibration artifact into a TPUCostModel, tolerating
    both the full artifact layout ({"calibrated": {...}}) and a flat
    field dict; unknown keys are ignored, absent ones keep defaults.
    Returns None (never raises) on a missing/corrupt file -- an
    unreadable calibration must degrade to the analytic defaults, not
    kill an allocation search."""
    import json
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None
    if isinstance(raw, dict) and isinstance(raw.get("calibrated"), dict):
        raw = raw["calibrated"]
    if not isinstance(raw, dict):
        return None
    fields = {f.name for f in dataclasses.fields(TPUCostModel)}
    kept = {k: float(v) for k, v in raw.items()
            if k in fields and isinstance(v, (int, float))}
    if not kept:
        return None
    return TPUCostModel(**kept)


def default_cost_model() -> TPUCostModel:
    """The cost model searches use when the caller passes none: a
    persisted on-chip calibration when present (``$REALHF_TPU_CALIBRATION``
    or ``./calibration_tpu.json``, written by ``scripts/calibrate.py``),
    else the analytic v5e defaults -- allocations stop being educated
    guesses as soon as one chip window has run the microbenchmark."""
    path = os.environ.get(CALIBRATION_ENV) or CALIBRATION_FILE
    cm = load_cost_model(path)
    if cm is None:
        return TPUCostModel()
    if path not in _calib_logged:
        _calib_logged.add(path)
        logger.info(
            "Cost model loaded from calibration %s: "
            "mxu_efficiency=%.3f, hbm_bw=%.0f GB/s", path,
            cm.mxu_efficiency, cm.hbm_bandwidth / 1e9)
    return cm


@dataclasses.dataclass
class MFCWorkload:
    """What one MFC costs, independent of layout."""
    name: str
    role: str
    interface_type: ModelInterfaceType
    fwd_flops: float                  # one forward over the batch
    param_bytes: float                # bf16 weight bytes
    train_state_bytes: float = 0.0    # weights+master+adam when training
    gen_tokens: int = 0               # decode steps (generate MFCs)
    n_layers: int = 0                 # for pipeline-stage divisibility
                                      # (0 = unknown: no pp candidates)

    @property
    def trainable(self) -> bool:
        return self.interface_type == ModelInterfaceType.TRAIN_STEP


@dataclasses.dataclass
class Candidate:
    parallel: ParallelismConfig
    dev_lo: int
    dev_hi: int
    time: float


@dataclasses.dataclass
class SearchResult:
    time: float                       # simulated step seconds
    assignment: Dict[str, Candidate]  # mfc name -> placement
    # roles whose searched slices are disjoint grouped onto different
    # model workers (filled by apply_searched_allocations)
    worker_assignment: Dict[str, int] = dataclasses.field(
        default_factory=dict)


def suggest_worker_assignment(workloads: List[MFCWorkload],
                              assignment: Dict[str, Candidate]
                              ) -> Dict[str, int]:
    """Role -> model-worker index realizing the simulator's slice
    concurrency: the runtime overlaps MFCs only across worker
    processes (each owning its devices), so roles whose searched
    device slices are disjoint go to different workers; overlapping
    slices share one."""
    spans: Dict[str, Tuple[int, int]] = {}
    for w in workloads:
        c = assignment[w.name]
        lo, hi = spans.get(w.role, (c.dev_lo, c.dev_hi))
        spans[w.role] = (min(lo, c.dev_lo), max(hi, c.dev_hi))
    # interval-merge sweep over role spans sorted by lo: overlapping
    # spans share one worker, disjoint spans get their own
    ordered = sorted(spans.items(), key=lambda kv: kv[1])
    out: Dict[str, int] = {}
    idx = -1
    cur_hi = -1
    for role, (lo, hi) in ordered:
        if lo >= cur_hi:  # disjoint from the running group
            idx += 1
            cur_hi = hi
        else:
            cur_hi = max(cur_hi, hi)
        out[role] = idx
    return out


def _pow2s(n: int) -> List[int]:
    out, p = [], 1
    while p <= n:
        out.append(p)
        p *= 2
    return out


def exec_time(w: MFCWorkload, tp: int, dp: int,
              cm: TPUCostModel, pp: int = 1) -> float:
    """Seconds for one execution of the MFC on dp*tp*pp chips.

    Pipeline stages add the schedule's bubble: (M + pp - 1) / M over
    perfect scaling at the engine's default microbatch count for the
    default 1F1B schedule (M = 4*pp -- its bounded residual memory
    affords twice GPipe's M, halving the (pp-1)/M overhead; see
    parallel/schedule.train_bubble_factor). pp candidates therefore
    price cheaper than under the old GPipe term and re-rank.
    """
    chips = tp * dp * pp
    if pp > 1:
        from realhf_tpu.parallel.schedule import train_bubble_factor
        bubble = train_bubble_factor(pp)
    else:
        bubble = 1.0
    if w.interface_type == ModelInterfaceType.TRAIN_STEP:
        flops = 3.0 * w.fwd_flops          # fwd + bwd (2x)
        return bubble * flops / (chips * cm.peak_flops
                                 * cm.mxu_efficiency)
    if w.interface_type == ModelInterfaceType.GENERATE:
        prefill = w.fwd_flops / (chips * cm.peak_flops
                                 * cm.mxu_efficiency)
        # decode is weight-bandwidth bound: every step re-reads this
        # chip's weight shard from HBM
        decode = w.gen_tokens * (w.param_bytes / tp) / cm.hbm_bandwidth
        if pp > 1:
            # pp-mesh generation runs on the collapsed dp x tp decode
            # view (engine.decode_engine): same per-chip decode traffic
            # at the view's tp (= train tp by default), plus one
            # weights reshard onto the view per weight version
            return (prefill + decode
                    + (w.param_bytes / chips) / cm.ici_bandwidth)
        return prefill + decode
    return bubble * w.fwd_flops / (chips * cm.peak_flops
                                   * cm.mxu_efficiency)


def enumerate_candidates(w: MFCWorkload, n_devices: int,
                         cm: TPUCostModel) -> List[Candidate]:
    """(slice, layout) placements whose per-chip memory fits."""
    need = w.train_state_bytes if w.trainable else w.param_bytes * 1.25
    out: List[Candidate] = []
    # GENERATE candidates stay pp=1 on purpose: a same-slice pp=1
    # candidate already models the colocated-rollout configuration
    # (overlapping slices serialize in the simulator, and the runtime
    # realizes it as either a realloc replica or the engine's decode
    # view -- both one extra gen-layout weight copy); a distinct pp>1
    # generate candidate would be redundant search space. exec_time
    # still prices pp>1 correctly for direct/profile callers.
    if w.interface_type == ModelInterfaceType.GENERATE or not w.n_layers:
        pps = [1]
    else:
        pps = [pp for pp in _pow2s(n_devices)
               if w.n_layers % pp == 0]
    for pp in pps:
        for tp in _pow2s(n_devices // pp):
            if need / (tp * pp) > cm.hbm_budget:
                continue
            for dp in _pow2s(n_devices // (tp * pp)):
                size = tp * dp * pp
                t = exec_time(w, tp, dp, cm, pp)
                for lo in range(0, n_devices - size + 1, size):
                    out.append(Candidate(
                        ParallelismConfig(data_parallel_size=dp,
                                          tensor_parallel_size=tp,
                                          pipeline_parallel_size=pp,
                                          sequence_parallel=(
                                              tp > 1 and w.trainable)),
                        lo, lo + size, t))
    if not out:  # nothing fits even at full TP: loud fallback
        logger.warning(
            "MFC %s does not fit the HBM budget at any layout on %d "
            "devices (%.1f GB/chip needed at full TP, budget %.1f GB);"
            " using full TP anyway -- expect OOM without remat/offload"
            " headroom.", w.name, n_devices,
            need / n_devices / 1e9, cm.hbm_budget / 1e9)
        out.append(Candidate(
            ParallelismConfig(data_parallel_size=1,
                              tensor_parallel_size=n_devices,
                              sequence_parallel=w.trainable),
            0, n_devices, exec_time(w, n_devices, 1, cm)))
    return out


# ---------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------
@dataclasses.dataclass
class _FlatProblem:
    workloads: List[MFCWorkload]
    n_devices: int
    cands: List[List[Candidate]]
    flat: List[Candidate]
    offsets: np.ndarray
    dev_lo: np.ndarray
    dev_hi: np.ndarray
    times: np.ndarray
    roles: np.ndarray
    trainable: np.ndarray
    dep_m: np.ndarray
    realloc: np.ndarray

    @property
    def n(self):
        return len(self.workloads)

    @property
    def m(self):
        return int(self.offsets[-1])

    def args(self):
        def ptr(arr, ct):
            return arr.ctypes.data_as(ctypes.POINTER(ct))
        return (self.n, self.n_devices,
                ptr(self.offsets, ctypes.c_int64),
                ptr(self.dev_lo, ctypes.c_int32),
                ptr(self.dev_hi, ctypes.c_int32),
                ptr(self.times, ctypes.c_double),
                ptr(self.roles, ctypes.c_int32),
                ptr(self.trainable, ctypes.c_int32),
                ptr(self.dep_m, ctypes.c_int8),
                ptr(self.realloc, ctypes.c_double),
                self.m)


def _flatten(workloads: List[MFCWorkload], deps: Dict[str, List[str]],
             n_devices: int, cm: TPUCostModel) -> _FlatProblem:
    n = len(workloads)
    cands = [enumerate_candidates(w, n_devices, cm) for w in workloads]
    offsets = np.zeros(n + 1, np.int64)
    for i, cl in enumerate(cands):
        offsets[i + 1] = offsets[i] + len(cl)
    m = int(offsets[-1])
    flat = [c for cl in cands for c in cl]

    name_idx = {w.name: i for i, w in enumerate(workloads)}
    dep_m = np.zeros((n, n), np.int8)
    for name, parents in deps.items():
        for p in parents:
            dep_m[name_idx[name], name_idx[p]] = 1

    role_ids: Dict[str, int] = {}
    cand_owner = np.concatenate(
        [np.full(len(cl), i) for i, cl in enumerate(cands)])
    # vectorized pairwise realloc cost: moving a role's weights
    # between two placements is bounded by the smaller slice's
    # aggregate ICI bandwidth; identical (layout, slice) pairs are
    # free. (The C++ simulator reads only same-role home->candidate
    # rows, but the dense numpy build is cheap.)
    lo = np.asarray([c.dev_lo for c in flat])
    hi = np.asarray([c.dev_hi for c in flat])
    sizes = hi - lo
    pbytes = np.asarray([workloads[int(o)].param_bytes
                         for o in cand_owner])
    chips = np.minimum(sizes[:, None], sizes[None, :])
    realloc = pbytes[:, None] / (chips * cm.ici_bandwidth)
    layout_key = np.asarray(
        [hash((c.parallel.data_parallel_size,
               c.parallel.tensor_parallel_size,
               c.parallel.pipeline_parallel_size,
               c.parallel.context_parallel_size,
               c.dev_lo, c.dev_hi)) for c in flat])
    realloc[layout_key[:, None] == layout_key[None, :]] = 0.0

    return _FlatProblem(
        workloads=workloads, n_devices=n_devices, cands=cands,
        flat=flat, offsets=offsets,
        dev_lo=np.asarray([c.dev_lo for c in flat], np.int32),
        dev_hi=np.asarray([c.dev_hi for c in flat], np.int32),
        times=np.asarray([c.time for c in flat], np.float64),
        roles=np.asarray([role_ids.setdefault(w.role, len(role_ids))
                          for w in workloads], np.int32),
        trainable=np.asarray([int(w.trainable) for w in workloads],
                             np.int32),
        dep_m=np.ascontiguousarray(dep_m.reshape(-1)),
        realloc=np.ascontiguousarray(realloc.reshape(-1)))


def search_rpc_allocations(
    workloads: List[MFCWorkload],
    deps: Dict[str, List[str]],
    n_devices: int,
    cost_model: Optional[TPUCostModel] = None,
    n_steps: int = 20000,
    seed: int = 1,
) -> SearchResult:
    """MCMC-search placements for the given MFC workloads.

    ``deps[name]`` lists MFCs that must finish before ``name`` starts
    (the DFG edges).
    """
    cm = cost_model or default_cost_model()
    lib = load_native()
    p = _flatten(workloads, deps, n_devices, cm)

    out_pick = np.zeros(p.n, np.int64)
    best = lib.mcmc_search(
        *p.args(), n_steps, 1.0, 1e4, seed,
        out_pick.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))

    assignment = {w.name: p.flat[int(out_pick[i])]
                  for i, w in enumerate(workloads)}
    logger.info("MCMC search: %d MFCs, %d candidates, best simulated "
                "step %.3fs", p.n, p.m, best)
    return SearchResult(time=float(best), assignment=assignment)


def simulate_named_assignment(
    workloads: List[MFCWorkload],
    deps: Dict[str, List[str]],
    n_devices: int,
    picks: Dict[str, Candidate],
    cost_model: Optional[TPUCostModel] = None,
) -> float:
    """Simulated step seconds for an explicit assignment (the same
    native simulator the search uses -- dependency + device-contention
    scheduling with realloc charges)."""
    cm = cost_model or default_cost_model()
    lib = load_native()
    p = _flatten(workloads, deps, n_devices, cm)

    def locate(i, c: Candidate) -> int:
        lo, hi = int(p.offsets[i]), int(p.offsets[i + 1])
        for j in range(lo, hi):
            f = p.flat[j]
            if (f.parallel.same_layout(c.parallel)
                    and (f.dev_lo, f.dev_hi) == (c.dev_lo, c.dev_hi)):
                return j
        raise ValueError(
            f"{workloads[i].name}: candidate {c} not enumerable")

    pick = np.asarray(
        [locate(i, picks[w.name]) for i, w in enumerate(workloads)],
        np.int64)
    return float(lib.simulate_assignment(
        *p.args(),
        pick.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))))


def calibrate_cost_model(
    spec,
    base: Optional[TPUCostModel] = None,
    probe_seqs: int = 4,
    probe_len: int = 512,
    probe_gen_tokens: int = 32,
    probe_layers: int = 4,
) -> TPUCostModel:
    """Measure-and-fit the cost model on the CURRENT backend
    (reference profiler-driven cost model,
    realhf/search_engine/estimate.py:323 + layers.py:56: per-layer
    fwd/bwd/opt timings feed the estimator; analytic rooflines rank
    candidates fine but mis-price realloc-vs-colocate trade-offs).

    For each distinct role architecture, a depth-truncated probe model
    (same hidden/ffn/vocab shapes, ``probe_layers`` layers -- per-layer
    cost is depth-linear, so achieved efficiency transfers) runs one
    timed train step and one timed decode on a single device. The
    returned model replaces ``mxu_efficiency`` with the measured
    train-step MFU and scales ``hbm_bandwidth`` by the measured decode
    bandwidth fraction."""
    import time

    import jax

    from realhf_tpu.api.config import ModelName
    from realhf_tpu.base import monitor
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.experiments.heuristic import _model_config_of
    from realhf_tpu.models import transformer as T
    from realhf_tpu.ops import functional as F
    from realhf_tpu.parallel.mesh import MeshContext, make_mesh

    cm = dataclasses.replace(base or TPUCostModel())
    mfus: List[float] = []
    bw_fracs: List[float] = []
    seen = set()
    for role, mspec in spec.models.items():
        cfg = _model_config_of(mspec)
        cfg.require_one_block(
            "the allocation search's calibration probe (its FLOPs are "
            "one dense block times n_layers)")
        key = (cfg.hidden_dim, cfg.intermediate_dim, cfg.n_q_heads,
               cfg.n_kv_heads, cfg.vocab_size, cfg.mlp_type)
        if key in seen:
            continue
        seen.add(key)
        probe = dataclasses.replace(
            cfg, n_layers=min(probe_layers, cfg.n_layers),
            is_critic=False, gradient_checkpointing=True)
        parallel = ParallelismConfig()
        mesh = make_mesh(parallel, devices=jax.devices()[:1])
        ctx = MeshContext(ModelName(f"probe_{role}", 0), mesh, parallel)
        params = T.init_params(probe, jax.random.PRNGKey(0))
        engine = Engine(probe, ctx, params,
                        optimizer=OptimizerConfig(
                            lr=1e-5, warmup_steps_proportion=0.0,
                            lr_scheduler_type="constant"),
                        total_train_steps=100)
        rng = np.random.default_rng(0)
        ids = rng.integers(2, probe.vocab_size,
                           size=(probe_seqs, probe_len)).astype(np.int32)
        seg = np.ones_like(ids)
        mb = dict(input_ids=ids, seg_ids=seg)

        def loss_fn(p, h, mb):
            lp = F.shifted_logprobs_from_hidden(
                probe, p, h, mb["input_ids"], mb["seg_ids"])
            return -lp.mean(), {}

        engine.train_batch([mb], loss_fn, loss_fn_key="calib")  # compile
        t0 = time.monotonic()
        engine.train_batch([mb], loss_fn, loss_fn_key="calib")
        train_s = time.monotonic() - t0
        flops = 4 * monitor.transformer_forward_flops(  # remat: 4x fwd
            n_layers=probe.n_layers, hidden_dim=probe.hidden_dim,
            n_q_heads=probe.n_q_heads, n_kv_heads=probe.n_kv_heads,
            head_dim=probe.head_dim,
            intermediate_dim=probe.intermediate_dim,
            vocab_size=probe.vocab_size,
            seqlens=[probe_len] * probe_seqs)
        mfus.append(flops / train_s / cm.peak_flops)

        from realhf_tpu.ops.sampling import GenerationHyperparameters
        from realhf_tpu.engine import packing
        prompts = [ids[i, :64] for i in range(probe_seqs)]
        pids, pseg, ppos = packing.left_padded_prompts(prompts, pad_id=0)

        def timed_gen(gn):
            g = GenerationHyperparameters(
                max_new_tokens=gn, min_new_tokens=gn, greedy=True,
                force_no_logits_mask=True)
            out = engine.generate(pids, pseg, ppos,
                                  jax.random.PRNGKey(0), g,
                                  eos_token_id=None, pad_token_id=0)
            jax.block_until_ready(out.tokens)  # compile
            t0 = time.monotonic()
            out = engine.generate(pids, pseg, ppos,
                                  jax.random.PRNGKey(1), g,
                                  eos_token_id=None, pad_token_id=0)
            jax.block_until_ready(out.tokens)
            return time.monotonic() - t0

        # Decode bandwidth from a TWO-POINT fit: one short and one long
        # generation share the prefill + sampling + dispatch overheads,
        # so the difference isolates pure per-token decode time (the
        # single-call version divided decode bytes by a wall that
        # included prefill, deflating the bandwidth estimate).
        gn_lo = max(2, probe_gen_tokens // 4)
        t_lo = timed_gen(gn_lo)
        t_hi = timed_gen(probe_gen_tokens)
        decode_s = max(t_hi - t_lo, 1e-6)
        pbytes = n_params(probe) * jnp_dtype_size(probe.param_dtype)
        decode_bytes = (probe_gen_tokens - gn_lo) * pbytes
        bw_fracs.append(decode_bytes / decode_s / cm.hbm_bandwidth)

    if mfus:
        cm.mxu_efficiency = float(np.clip(np.median(mfus), 0.01, 1.0))
    if bw_fracs:
        cm.hbm_bandwidth *= float(np.clip(np.median(bw_fracs), 0.01, 1.0))
    logger.info(
        "Calibrated cost model: mxu_efficiency=%.3f (measured MFUs %s), "
        "effective HBM bw %.0f GB/s (fracs %s)", cm.mxu_efficiency,
        [round(m, 3) for m in mfus], cm.hbm_bandwidth / 1e9,
        [round(b, 3) for b in bw_fracs])
    return cm


def jnp_dtype_size(dtype_name: str) -> int:
    import jax.numpy as jnp
    return jnp.dtype(dtype_name).itemsize


def workloads_from_spec(spec, gen_tokens: int = 256,
                        avg_seqlen: int = 512) -> Tuple[
                            List[MFCWorkload], Dict[str, List[str]]]:
    """Derive workloads + dependency lists from an ExperimentSpec."""
    from realhf_tpu.api.dfg import DFG
    from realhf_tpu.base import monitor
    from realhf_tpu.experiments.heuristic import _model_config_of

    dfg = DFG(spec.mfcs)
    out = []
    for node in dfg.nodes:
        cfg = _model_config_of(spec.models[node.role])
        cfg.require_one_block(
            "the allocation search's cost model (base/monitor.py "
            "counts one dense block times n_layers)")
        seqlens = [avg_seqlen] * node.n_seqs
        fwd = monitor.transformer_forward_flops(
            n_layers=cfg.n_layers, hidden_dim=cfg.hidden_dim,
            n_q_heads=cfg.n_q_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim,
            intermediate_dim=cfg.intermediate_dim,
            vocab_size=cfg.vocab_size, seqlens=seqlens)
        pbytes = n_params(cfg) * 2.0
        out.append(MFCWorkload(
            name=node.name, role=node.role,
            interface_type=node.interface_type,
            fwd_flops=float(fwd), param_bytes=pbytes,
            train_state_bytes=n_params(cfg) * 18.0,
            n_layers=cfg.n_layers,
            gen_tokens=(gen_tokens if node.interface_type
                        == ModelInterfaceType.GENERATE else 0)))
    deps = {n.name: [p.name for p in n.parents] for n in dfg.nodes}
    return out, deps


def apply_searched_allocations(spec, n_devices: int,
                               cost_model: Optional[TPUCostModel] = None,
                               n_steps: int = 20000,
                               gen_tokens: int = 256,
                               avg_seqlen: int = 512) -> SearchResult:
    """allocation_mode=search: run the MCMC search and write the
    resulting layouts into the spec (role primaries from train MFCs,
    per-MFC overrides elsewhere), like apply_heuristic_allocations.

    The simulator's slice-level CONCURRENCY is realized by the runtime
    only across model-worker processes (each owning its own devices):
    the result carries ``worker_assignment`` for that; in inline mode
    (one process, serial MFCs) only the layouts apply and the
    simulated time is optimistic about overlap.
    """
    workloads, deps = workloads_from_spec(spec, gen_tokens, avg_seqlen)
    res = search_rpc_allocations(workloads, deps, n_devices,
                                 cost_model, n_steps)
    res.worker_assignment = suggest_worker_assignment(workloads,
                                                      res.assignment)
    primaries: Dict[str, ParallelismConfig] = {}
    for w in workloads:
        if w.trainable:
            primaries[w.role] = res.assignment[w.name].parallel
    for w in workloads:
        primaries.setdefault(w.role, res.assignment[w.name].parallel)
    for role, par in primaries.items():
        spec.models[role] = dataclasses.replace(spec.models[role],
                                                parallel=par)
    spec.allocations = dict(spec.allocations)
    for w in workloads:
        par = res.assignment[w.name].parallel
        if not par.same_layout(primaries[w.role]):
            spec.allocations[w.name] = par
    return res
