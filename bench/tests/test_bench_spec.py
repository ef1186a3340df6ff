"""BENCHMARK.json and the files it names agree with each other and with
the contract the harness relies on: every name resolves to a file, every
per-layer metric has a reader, every cell reports what it must."""
import json
import re

import pytest

from bench import run
from bench.model import CONTRACT, arch_path, load_arch, load_config
from bench.traffic import load_mix

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_keys():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert "bound" not in m


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(run.load_reader(metric))


@pytest.mark.parametrize("cell", SPEC["workloads"],
                         ids=[w["name"] for w in SPEC["workloads"]])
def test_cell_files_resolve(cell):
    conf = load_config(cell["config"])
    mix = load_mix(cell["traffic"])
    entry = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert entry["file"] == f"bench/configs/{cell['config']}.json"
    assert conf["name"] == cell["config"]
    # the architecture is found by the file its reference names
    assert arch_path(conf).is_file()
    arch = load_arch(conf)
    assert all(callable(getattr(arch, name, None)) for name in CONTRACT)
    for key in entry["reduced"]:
        assert key in conf["reduced"]
    srv = conf["serving"]
    assert srv["n_pages"] >= srv["hbm_pages"] > 0
    assert mix["max_context"] <= conf["max_position_embeddings"]
    assert mix["history"]["max"] < mix["max_context"]
    per_layer = [m for m in SPEC["per_layer"]
                 if cell["name"] in m.get("workloads", [cell["name"]])]
    assert per_layer


def test_lattice_covers_every_bucket_of_a_mix():
    """The warmed buckets are the backend's: every lane count to the
    batch, every chunk to the budget's + 1 (a returning turn's pending
    token), and every table width from the shortest history's to the
    context cap's."""
    from types import SimpleNamespace
    from bench.serve import Cell
    cell = SimpleNamespace(conf=dict(serving=dict(token_budget=512,
                                                  max_batch=32)),
                           mix=dict(history=dict(min=128),
                                    max_context=4096), page=16)
    got = Cell.lattice(cell)
    assert {b for b, _, _ in got} == {1, 2, 4, 8, 16, 32}
    assert {t for _, t, _ in got} == {1 << k for k in range(11)}
    assert {w for _, _, w in got} == {16, 32, 64, 128, 256}
    assert len(got) == 6 * 11 * 5


def test_lattice_holds_every_bucket_the_backend_picks():
    """Any step the window can dispatch lands on a warmed bucket, by the
    backend's own bucketing: lanes up to the batch, chunks up to the
    budget plus the pending token, contexts between the shortest history
    and the cap."""
    from types import SimpleNamespace
    from bench.serve import Cell, _pages
    from repro.serving.backend import _bucket
    cell = SimpleNamespace(conf=dict(serving=dict(token_budget=512,
                                                  max_batch=32)),
                           mix=dict(history=dict(min=128),
                                    max_context=4096), page=16)
    got = set(Cell.lattice(cell))
    for B in (1, 3, 17, 32):
        for q in (1, 2, 5, 100, 512, 513):
            for ctx in (129, 700, 2049, 4096):
                assert (_bucket(B), _bucket(q),
                        _bucket(_pages(ctx, 16))) in got
