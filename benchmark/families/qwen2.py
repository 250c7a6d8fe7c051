"""The program's ``qwen2`` family (Qwen2, Qwen2.5): llama-like, with
q/k/v biases and, in the small models, a tied head."""

from benchmark.families.llama_like import *  # noqa: F401,F403
