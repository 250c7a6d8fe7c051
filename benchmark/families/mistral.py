"""The program's ``mistral`` family: llama-like, no biases, untied
head. Runs without a sliding window (Mistral-7B-v0.3 publishes
``sliding_window: null``); one that is set is refused."""

from benchmark.families.llama_like import *  # noqa: F401,F403
