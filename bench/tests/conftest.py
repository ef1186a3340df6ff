"""Shared small sizes for the benchmark's CPU tests.

`tiny` is every configuration in ``BENCHMARK.json`` in turn, shrunk to
widths the CPU runs in seconds; `tiny_mix` is a short mix on the same
generator.  The chip entry (`bench/run.py main`) is never run to a
result here: the tests drive `run.measure` and `serve.run` directly,
with CPU devices."""
import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.model import load_arch, load_config  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def shrink(conf: dict) -> dict:
    """Small sizes for the keys every configuration shares; the
    architecture module's own `shrink`, where it has one, sets the keys
    only it has."""
    c = copy.deepcopy(conf)
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
             vocab_size=256)
    c["serving"].update(page_size=8, hbm_pages=128, n_pages=128, max_batch=2,
                        token_budget=16)
    arch = load_arch(c)
    return arch.shrink(c) if hasattr(arch, "shrink") else c


@pytest.fixture
def shrink_conf():
    """`shrink`, for a test that shrinks a configuration of its own."""
    return shrink


@pytest.fixture(params=[c["name"] for c in SPEC["configs"]])
def tiny(request):
    return shrink(load_config(request.param))


@pytest.fixture
def tiny_mix():
    mix = json.loads((ROOT / "bench" / "traffic"
                      / "chat_resident.json").read_text())
    mix.update(
        history=dict(median=24, sigma=0.8, min=16, max=40,
                     over_capacity=0.6),
        new_tokens=dict(median=8, sigma=0.9, min=4, max=16),
        output_tokens=dict(median=6, sigma=0.5, min=3, max=10),
        max_context=64,
        arrivals=dict(process="poisson", rate_per_s=4.0),
        check=dict(sample_tokens=24, max_requests=4, max_gap_over_std=0.1,
                   min_tokens_compared=8))
    return mix
