#!/usr/bin/env python
"""Multi-turn agentic rollout harness (ISSUE 11 bench satellite).

Measures the environment-in-the-loop episode path two ways over the
same tiny model and the same tool-game episodes:

- **serving**: an :class:`EpisodeRunner` driving N concurrent
  episodes through a REAL ``RolloutServer`` (continuous batching,
  weight-version stamps) over ZMQ -- the production shape where env
  steps for one episode overlap generation for the others.
- **local**: the same runner over the in-process
  ``LocalRolloutBackend`` (the inline-runner / tier-1 path; batched
  synchronous generation, no overlap possible).

Reports episodes/s, **turns/s**, and the env-step vs generation
overlap fraction (wall-clock inside ``env.step`` while other requests
were in flight / total env-step wall) as one JSON line.

Usage::

    python scripts/bench_agentic.py [--episodes 16] [--turns 3]
        [--concurrent 8] [--new-tokens 4] [--env-delay-ms 2]
"""
import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = dict(
    n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
    intermediate_dim=64, vocab_size=97, apply_rotary=True,
    layer_norm_type="rms", mlp_type="llama", use_attention_bias=False,
    use_attn_proj_bias=False, use_mlp_bias=False,
    activation_function="silu", compute_dtype="float32")


class _DelayedToolGame:
    """tool_game with a configurable env-step latency -- a stand-in
    for a real tool executor (sandbox, search, checker process); the
    delay is what the serving path can overlap with generation."""

    def __init__(self, inner, delay_s: float):
        self._inner = inner
        self._delay = delay_s

    def reset(self):
        return self._inner.reset()

    def step(self, action):
        if self._delay > 0:
            time.sleep(self._delay)
        return self._inner.step(action)


def _episodes(n, n_turns, delay_s, seed=0):
    import numpy as np

    from realhf_tpu.agentic.env import make_env
    rng = np.random.default_rng(seed)
    for i in range(n):
        prompt = rng.integers(4, TINY["vocab_size"], size=4) \
            .astype(np.int32)
        yield (i, _DelayedToolGame(
            make_env("tool_game", prompt=prompt, seed=i,
                     vocab_size=TINY["vocab_size"], n_turns=n_turns),
            delay_s))


def _build_backend(params, *, new_tokens, n_slots, max_prompt_len):
    from realhf_tpu.engine.inflight import InflightBatchingGenerator
    from realhf_tpu.models.config import TransformerConfig
    from realhf_tpu.ops.sampling import GenerationHyperparameters

    cfg = TransformerConfig(**TINY)
    g = GenerationHyperparameters(
        max_new_tokens=new_tokens, min_new_tokens=new_tokens,
        greedy=True, force_no_logits_mask=True)
    return InflightBatchingGenerator(
        cfg, params, g, n_slots=n_slots,
        max_prompt_len=max_prompt_len, eos_token_id=None,
        pad_token_id=0, chunk_size=new_tokens)


def _run_serving(params, args) -> dict:
    from realhf_tpu.agentic.episode import EpisodeRunner
    from realhf_tpu.serving.request_queue import RequestQueue
    from realhf_tpu.serving.server import RolloutClient, RolloutServer

    max_prompt = 8 + args.turns * (args.new_tokens + 2) + 8
    server = RolloutServer(
        _build_backend(params, new_tokens=args.new_tokens,
                       n_slots=args.concurrent,
                       max_prompt_len=max_prompt),
        server_name="agentic-bench/0",
        queue=RequestQueue(max_depth=512, n_slots=args.concurrent),
        stream_tokens=False)
    stop = threading.Event()
    thread = threading.Thread(
        target=lambda: server.serve_forever(stop, poll_timeout=0.002),
        daemon=True)
    thread.start()
    client = RolloutClient(server.address)
    try:
        runner = EpisodeRunner(
            client,
            _episodes(args.episodes, args.turns,
                      args.env_delay_ms / 1000.0),
            max_concurrent=args.concurrent, max_turns=args.turns + 1,
            max_seq_len=max_prompt, ttl=120.0)
        t0 = time.monotonic()
        eps = runner.run_all(deadline_secs=600.0)
        wall = time.monotonic() - t0
    finally:
        stop.set()
        thread.join(timeout=10.0)
        client.close()
        server.close()
    return _report("serving", runner, eps, wall)


def _run_local(params, args) -> dict:
    import numpy as np

    from realhf_tpu.agentic.episode import EpisodeRunner
    from realhf_tpu.agentic.local import GenResult, LocalRolloutBackend

    max_prompt = 8 + args.turns * (args.new_tokens + 2) + 8
    backend = _build_backend(params, new_tokens=args.new_tokens,
                             n_slots=args.concurrent,
                             max_prompt_len=max_prompt)

    import jax
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 100000))

    def generate(prompts):
        # drive the slot backend synchronously (the inline path runs
        # the engine's batched generate; the slot API reuses the same
        # compiled fns and keeps this script to one model build)
        outs = backend.generate_all(prompts, next(keys))
        return [GenResult(tokens=np.asarray(o.tokens, np.int32),
                          logprobs=np.asarray(o.logprobs, np.float32),
                          no_eos=bool(o.no_eos)) for o in outs]

    runner = EpisodeRunner(
        LocalRolloutBackend(generate),
        _episodes(args.episodes, args.turns,
                  args.env_delay_ms / 1000.0),
        max_concurrent=args.concurrent, max_turns=args.turns + 1,
        max_seq_len=max_prompt)
    t0 = time.monotonic()
    eps = runner.run_all(deadline_secs=600.0)
    wall = time.monotonic() - t0
    return _report("local", runner, eps, wall)


def _report(mode, runner, eps, wall) -> dict:
    import numpy as np
    st = runner.stats()
    rewards = [ep.total_reward for ep in eps]
    return dict(
        mode=mode,
        episodes=len(eps),
        turns=st["turns_done"],
        wall_s=round(wall, 3),
        episodes_per_sec=round(len(eps) / max(wall, 1e-9), 4),
        turns_per_sec=round(st["turns_done"] / max(wall, 1e-9), 4),
        env_step_secs=st["env_step_secs"],
        env_step_overlap_secs=st["env_step_overlap_secs"],
        env_gen_overlap_frac=round(
            st["env_step_overlap_secs"]
            / max(st["env_step_secs"], 1e-9), 4),
        mean_episode_reward=round(float(np.mean(rewards))
                                  if rewards else 0.0, 4),
        env_errors=st["env_errors"], abandoned=st["abandoned"])


def run(args) -> dict:
    import jax

    from realhf_tpu.models import transformer as T
    from realhf_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(**TINY)
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    local = _run_local(params, args)
    serving = _run_serving(params, args)
    return dict(
        backend=jax.default_backend(),
        config=dict(episodes=args.episodes, turns=args.turns,
                    concurrent=args.concurrent,
                    new_tokens=args.new_tokens,
                    env_delay_ms=args.env_delay_ms),
        local=local, serving=serving,
        note=("tiny-model CPU harness: the load-bearing signals are "
              "turns/s and env_gen_overlap_frac -- the serving path "
              "overlaps env steps with other episodes' generation; "
              "the local (inline) path cannot by construction"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--episodes", type=int, default=16)
    ap.add_argument("--turns", type=int, default=3)
    ap.add_argument("--concurrent", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=4)
    ap.add_argument("--env-delay-ms", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    out = run(args)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
