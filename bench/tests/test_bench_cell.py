"""One cell loop at small sizes on the CPU, through `run.measure` (never
the command's chip path): the last line's keys, the comparison with the
reference, and that a timed path broken underneath reads not correct."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run, serve

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
PEAK = dict(bf16_flops=1e12, hbm_bytes_per_s=1e11)


def _cell(conf):
    """The first benchmark cell of a configuration, run here on its small
    sizes."""
    return next(w for w in SPEC["workloads"] if w["config"] == conf["name"])


def _measure(conf, mix, seed, traced=False, **kw):
    return run.measure(SPEC, _cell(conf), conf, mix, seed, 3.0, traced,
                       PEAK, jax.devices(), kernel_mode="ref", **kw)


def _applying(kind, conf):
    return [m for m in SPEC[kind] if run.applies(m, _cell(conf)["name"])]


def test_cell_loop_result_line(tiny, tiny_mix, capsys):
    out = _measure(tiny, tiny_mix, 2**31 + 11)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "window_compiles", "compared"]
    assert out["window_compiles"] == 0
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = _applying("end_to_end", tiny)
    assert set(out["metrics"]) == {m["name"] for m in e2e}
    for m in e2e:
        got = out["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    json.dumps(out)
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-2].startswith("compared max_logit_gap_over_std")
    assert err[-1].startswith("compared tokens_compared")


def test_traced_run_reports_per_layer_metrics(tiny, tiny_mix):
    out = _measure(tiny, tiny_mix, 12, traced=True)
    assert out["correct"] is True, out["compared"]
    names = {m["name"] for m in _applying("per_layer", tiny)}
    assert set(out["metrics"]) <= names
    # host-side readers find something on any platform; the device-trace
    # ones need a device plane, which the CPU run does not write
    assert {"engine.queue_wait_p95_ms", "step_mfu"} & names \
        <= set(out["metrics"])
    assert set(out["device"]) >= {"busy_s", "window_s"}
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "compared"


def _keep_state(cell):
    """The step returns the pools it was given: no KV is ever written."""
    model = cell.be.model
    step = model.step_paged

    def broken(params, ids, k, v, *a, **kw):
        k0, v0 = jnp.copy(k), jnp.copy(v)
        toks, logits, _, _ = step(params, ids, k, v, *a, **kw)
        return toks, logits, k0, v0
    model.step_paged = broken


def _alter_token(cell):
    """Every token is changed where the step produces it."""
    model = cell.be.model
    step = model.step_paged
    vocab = cell.cfg.vocab

    def broken(*a, **kw):
        toks, logits, k, v = step(*a, **kw)
        return (toks + 1) % vocab, logits, k, v
    model.step_paged = broken


@pytest.mark.parametrize("fault", [_keep_state, _alter_token],
                         ids=["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(tiny, tiny_mix, fault):
    out = _measure(tiny, tiny_mix, 2**31 + 11, fault=fault)
    assert out["correct"] is False
    gap = out["compared"]["max_logit_gap_over_std"]
    assert gap["value"] > gap["limit"]


def test_fp8_control_is_not_correct(tiny, tiny_mix):
    """The reference computed in fp8 in the program's place reads above
    the limit on the same requests, while the program reads below it."""
    cell = serve.Cell(tiny, tiny_mix, 5, 3.0, kernel_mode="ref")
    cell.warm()
    cell.fill()
    win = cell.window(3.0)
    params = cell.params
    cell.free()
    done = [r for r in win.records if r.done is not None]
    assert done
    limit = tiny_mix["check"]["max_gap_over_std"]
    sound = max(serve.gaps(tiny, params, r).max() for r in done)
    control = max(serve.gaps(tiny, params, r, fp8=True).max() for r in done)
    assert sound <= limit < control
