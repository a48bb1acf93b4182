"""``correct`` at a size the CPU holds: the sound program passes, every
fault a cell can have fails, and so does the control (the reference in the
precision next below the configuration's).  The cells keep their traffic
and their limits; only the population (MLR) or the widths (LM) shrink.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import json
import shutil
import time

import jax
import pytest

from chipbench import control, harness

SMALL = {
    "femnist-mlr": {"clients": 8, "samples_per_client": 64},
    "olmo-1b": {"d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
                "d_ff": 128, "vocab_size": 512, "n_layers": 2},
}
SMALL_TRAFFIC = {"silo2": {"seq_len": 64, "batch_pool": 4}}
CELLS = {"mlr": "femnist.full", "lm": "olmo1b.silo2"}
_BENCHED = {w["name"] for w in harness.load_json(harness.REPO / "BENCHMARK.json")["workloads"]}
KINDS = [k for k, w in CELLS.items() if w in _BENCHED]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark's files with each configuration shrunk."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copy(harness.REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(harness.HERE, root / harness.PKG,
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
    bench = harness.load_json(root / "BENCHMARK.json")
    for conf in bench["configs"]:
        path = root / conf["file"]
        path.write_text(json.dumps(dict(harness.load_json(path), **SMALL[conf["name"]])))
    for name, small in SMALL_TRAFFIC.items():
        path = root / harness.PKG / "traffic" / f"{name}.json"
        path.write_text(json.dumps(dict(harness.load_json(path), **small)))
    return root


def _run(root, kind, seed=1234567890123):
    return harness.run(CELLS[kind], seed, 0.2, False, time.perf_counter(),
                       root=root, check=False, out_dir=root / "out")


def _unchanged(monkeypatch):
    """A round that returns its state unchanged (but its counter)."""
    from repro.core import gpdmm

    real = gpdmm._round

    def broken(cfg, state, *a, **k):
        new, metrics = real(cfg, state, *a, **k)
        return dict(state, round=new["round"]), metrics

    monkeypatch.setattr(gpdmm, "_round", broken)


def _half_batch(monkeypatch):
    """Half of every client's batch left out, the mean taken over the rest."""
    from repro.core.api import make_oracle
    from repro.core.softmax import SoftmaxRegression
    from repro.models import model

    real_oracle = SoftmaxRegression.oracle

    def oracle(self):
        o = real_oracle(self)
        half = lambda b: jax.tree.map(lambda t: t[:, : t.shape[1] // 2], b)  # noqa: E731
        return make_oracle(o, grad_arena=lambda spec: (
            lambda x, b: o.grad_arena(spec)(x, half(b))))

    monkeypatch.setattr(SoftmaxRegression, "oracle", oracle)
    real_loss = model.loss_fn
    monkeypatch.setattr(model, "loss_fn", lambda cfg, p, b: real_loss(
        cfg, p, {k: v[:, : v.shape[1] // 2] for k, v in b.items()}))


@pytest.mark.parametrize("kind", KINDS)
def test_sound_program_is_correct(root, kind):
    res = _run(root, kind)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 3


@pytest.mark.parametrize("fault", [_unchanged, _half_batch], ids=["unchanged", "half_batch"])
@pytest.mark.parametrize("kind", KINDS)
def test_broken_round_is_not_correct(root, kind, fault, monkeypatch):
    fault(monkeypatch)
    res = _run(root, kind)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("kind", KINDS)
def test_control_fails_its_limits(root, kind):
    rows = control.readings(CELLS[kind], [77], 1, root=root, check=False)
    limits = harness.cell(CELLS[kind], root)["limits"]
    ctl = [r[2] for r in rows if r[1] == "control"][0]
    assert any(ctl[k] > lim for k, lim in limits.items()), (ctl, limits)
    prog = [r[2] for r in rows if r[1] == "program"][0]
    assert all(prog[k] <= lim for k, lim in limits.items()), (prog, limits)
