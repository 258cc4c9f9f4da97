"""Helpers for the benchmark's own tests: cells at a size the CPU holds.

These tests drive the harness without a chip (the chip check lives in
run.py, before any of this); they are not among the repository's tests
and run with

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import json
import os
import sys
import time
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def limits(cell: str) -> dict:
    with open(os.path.join(BENCH, "limits", cell + ".json")) as f:
        return json.load(f)["limits"]


TINY_LM = {
    "kind": "dense_lm_serve", "arch": "deepseek-7b", "members": 2,
    "n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
    "head_dim": 32, "d_ff": 256, "vocab_size": 2048, "rope_theta": 10000.0,
    "norm_eps": 1e-6, "dtype": "bfloat16"}

TINY_CHAT = {
    "kind": "open_loop", "rate_rps": 3.0, "ramp_s": 1.0,
    "work_seed": 7,
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                      "min": 8, "max": 64},
    "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 32},
    "first_token_wait_s": 30, "drain_s": 30,
    "engine": {"slots": 4, "page_size": 16, "kv_dtype": "f32",
               "n_pages": 40, "prefill_budget": None}}

# four steps a round at the cell's lr leave the members' outputs near
# uniform, where one member's distribution and the ensemble's hardly
# differ; lr 0.05 parts them, as the cell's 391 steps do
TINY_NIN = {
    "kind": "nin_ec", "arch": "paper_nin", "members": 2,
    "layers": [["conv", 192, 5, 1], ["conv", 160, 1, 1], ["conv", 96, 1, 1],
               ["maxpool", 0, 3, 2],
               ["conv", 192, 5, 1], ["conv", 192, 1, 1], ["conv", 192, 1, 1],
               ["avgpool", 0, 3, 2],
               ["conv", 192, 3, 1], ["conv", 192, 1, 1]],
    "img": 32, "channels": 3, "n_classes": 100, "per_member": 32,
    "batch": 8, "tau_steps": 4, "p_steps": 2, "lam": 0.5,
    "relabel_fraction": 0.25, "lr": 0.05, "momentum": 0.9, "l2": 1e-4,
    "bias_std": 0.05}

# the LM EC round at a size the CPU holds, in float32: the CPU computes
# the program's float32 as the reference does, so a sound run reads at
# round-off and the test holds the harness (each fault caught, the
# control failing) to the cell's own limits, which bfloat16 runs on the
# chip set; lr 0.5 parts the members at this size, as the cell's do
TINY_LM_EC = dict(
    TINY_LM, kind="lm_ec", n_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
    head_dim=32, d_ff=128, vocab_size=256, dtype="float32", seq_len=32,
    batch=1, per_member=4, tau_steps=4, p_steps=2, lam=0.5,
    relabel_fraction=0.75, label_mode="topk", top_m=8, lr=0.5,
    momentum=0.9)
LM_MIX = {"kind": "ec_rounds", "warm_rounds": 2, "test_sequences": 2}


def ctx(config: dict, mix: dict, cell: str, seed: int, seconds: float,
        **limit_overrides) -> SimpleNamespace:
    """A cell's context at a tiny size, held to the cell's own limits
    (but for counts that only a full-size window reaches)."""
    return SimpleNamespace(
        spec=None, cell={"name": cell, "chips": 1}, config=config, mix=mix,
        limits=dict(limits(cell), **limit_overrides), trace_dir=None,
        seed=seed, seconds=seconds, t_start=time.time())
