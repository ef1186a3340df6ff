"""Architectures are found by file: the dense decoder's module gives, bit
for bit, the weights, reference logits and counts the harness had before
the architecture moved into it; a configuration whose reference names no
file stops `run.main` before set-up; and a new module, written anywhere
the loader is pointed at, is driven through a cell with no other edit."""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import model, run, serve
from bench.model import CONTRACT, load_arch, load_config, make_params

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
# the shared keys at the CPU tests' small sizes (conftest's `shrink`),
# written out so that the pins below do not move with the fixtures
SMALL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
             vocab_size=256)
SEED = 2**31 + 7
IDS = np.random.default_rng(3).integers(0, 256, 1100)
AT = [0, 5, 511, 512, 1023, 1099]     # both sides of a block and a pad edge


def _sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def small_yi():
    conf = dict(load_config("yi-6b-8l"), **SMALL)
    arch = load_arch(conf)
    cfg = arch.model_config(conf)
    return conf, arch, make_params(arch.param_shapes(cfg), cfg.dtype, SEED)


def test_pinned_weights(small_yi):
    _, _, params = small_yi
    leaves = jax.tree.leaves(params)
    assert len(leaves) == 12
    assert _sha(np.asarray(a) for a in leaves) == \
        "40b1d79823dd0261bb316723adf3a9a5ab526da982c92da97e3c60205d1ef2b8"


@pytest.mark.parametrize("fp8,want,col3", [
    (False, "3ac64609bedc38b25c9214b8bc0c778dcc86addc5e3c4322d6632a975da34353",
     [0.09543270617723465, -0.005387710873037577, -0.12154289335012436,
      -0.0551411509513855, 0.06052146106958389, 0.18028967082500458]),
    (True, "84d54a0cfcc06d17d51604b5e3fcd77a3c259255507b8a467b038f56d929a758",
     [0.0764281302690506, -0.0488896407186985, -0.11297605186700821,
      -0.023700427263975143, 0.10594139248132706, 0.16894792020320892]),
], ids=["reference", "fp8_control"])
def test_pinned_logits(small_yi, fp8, want, col3):
    conf, arch, params = small_yi
    out = arch.logits(conf, params, IDS, AT, fp8=fp8)
    assert out.shape == (len(AT), 256) and out.dtype == np.float32
    assert out[:, 3].tolist() == col3
    assert _sha([out]) == want


def test_pinned_counts_at_published_widths():
    conf = load_config("yi-6b-8l")
    arch = load_arch(conf)
    cfg = arch.model_config(conf)
    # two decode lanes, a 256-token chunk, a lane across a page edge
    lanes = [(1000, 1, True), (3071, 1, True), (512, 256, True),
             (15, 2, False)]
    assert arch.step_flops(cfg, lanes) == 743345225728
    assert arch.step_attn(cfg, lanes, 16, 2) == (22029795328, 124518400)
    assert arch.step_attn(cfg, lanes, 16, 3) == (22029795328, 169738240)
    assert arch.kv_token_bytes(cfg) == 16384


def test_unknown_reference_stops_before_set_up(tmp_path, monkeypatch,
                                               capsys):
    cell = SPEC["workloads"][0]
    monkeypatch.setattr(model, "ARCH", tmp_path)   # holds no module
    assert run.main(["--workload", cell["name"], "--seed", "1",
                     "--seconds", "1"]) == 2
    missing = tmp_path / f"{load_config(cell['config'])['reference']}.py"
    assert str(missing) in capsys.readouterr().err


# A new architecture: another module's code under a new name, each entry
# counted.  BASE_FILE is replaced by the module it stands on.
STUB = '''"""A stand-in architecture, to show that one is added as a file."""
import importlib.util

_spec = importlib.util.spec_from_file_location("stub_base", BASE_FILE)
BASE = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(BASE)
CALLS = []


def _counted(name):
    def entry(*args, **kwargs):
        CALLS.append(name)
        return getattr(BASE, name)(*args, **kwargs)
    return entry


for _name in CONTRACT:
    globals()[_name] = _counted(_name)


def shrink(conf):
    CALLS.append("shrink")
    return BASE.shrink(conf) if hasattr(BASE, "shrink") else conf
'''


def test_new_architecture_is_files_only(tiny, tiny_mix, shrink_conf,
                                        tmp_path, monkeypatch):
    base = model.arch_path(tiny)
    (tmp_path / "stub_arch.py").write_text(
        STUB.replace("BASE_FILE", repr(str(base)))
        .replace("CONTRACT", repr(CONTRACT)))
    monkeypatch.setattr(model, "ARCH", tmp_path)
    conf = shrink_conf(dict(load_config(tiny["name"]),
                            reference="stub_arch"))
    cell = serve.Cell(conf, tiny_mix, 9, 3.0, kernel_mode="ref")
    stub = cell.arch
    assert stub.__file__ == str(tmp_path / "stub_arch.py")
    cell.warm()
    cell.fill()
    win = cell.window(3.0)
    params = cell.params
    cell.free()
    done = [r for r in win.records if r.done is not None]
    assert done and win.step_flops > 0 and win.attn_steps
    worst = max(serve.gaps(conf, params, r).max() for r in done)
    assert worst <= tiny_mix["check"]["max_gap_over_std"]
    assert set(stub.CALLS) == set(CONTRACT) | {"shrink"}
