"""The benchmark's own pieces on the CPU: loading by name, the trace
reduction, the work counts and the device check.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import json
import shutil
import types

import numpy as np
import pytest

from chipbench import counts, harness, trace

REPO = harness.REPO
TESTDATA = harness.HERE / "testdata"


def _bench():
    return harness.load_json(REPO / "BENCHMARK.json")


# -- loading by name -----------------------------------------------------------

@pytest.mark.parametrize("workload", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_loads_by_name(workload):
    c = harness.cell(workload)
    assert c["objective"].exists()
    assert c["config"]["name"] == c["workload"]["config"]
    assert set(c["limits"]) <= {"drift_gap", "update1_gap", "update3_gap",
                                "client3_gap", "dual3_gap"}
    assert {m["name"] for m in c["end_to_end"]} >= {"round_s", "setup_s"}
    assert c["per_layer"]
    for m in c["per_layer"]:
        assert callable(harness.load_module(m["reader"]).read)


@pytest.mark.parametrize("conf", _bench()["configs"], ids=lambda c: c["name"])
def test_every_config_file_states_its_cut(conf):
    cfg = harness.load_json(REPO / conf["file"])
    assert cfg["name"] == conf["name"]
    assert cfg["reduced"] == conf["reduced"]
    assert (harness.HERE / "objectives" / f"{cfg['objective']}.py").exists()


def _copy_root(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.HERE, tmp_path / harness.PKG,
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
    return tmp_path


def test_extra_cell_config_and_metric_are_found_as_files(tmp_path):
    root = _copy_root(tmp_path)
    pkg = root / harness.PKG
    bench = harness.load_json(root / "BENCHMARK.json")
    mlr = {c["name"]: c for c in bench["configs"]}["femnist-mlr"]
    conf = dict(mlr, name="femnist-mlr.small",
                file=f"{harness.PKG}/configs/femnist-mlr.small.json")
    cfg = harness.load_json(REPO / mlr["file"])
    (pkg / "configs" / "femnist-mlr.small.json").write_text(
        json.dumps(dict(cfg, name="femnist-mlr.small", clients=355)))
    (pkg / "traffic" / "k1.json").write_text(json.dumps(
        {"algorithm": "gpdmm", "inner_steps": 1, "eta": 0.05,
         "participation": 1.0, "batch": 32, "checked_rounds": 3}))
    (pkg / "limits" / "femnist.k1.json").write_text(json.dumps(
        {"limits": {"update1_gap": 0.01}}))
    (pkg / "metrics" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return float(ctx['rounds'])\n")
    bench["configs"].append(conf)
    bench["workloads"].append({"name": "femnist.k1", "config": "femnist-mlr.small",
                               "traffic": "k1", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "round driver", "moves": "round_s",
                               "workloads": ["femnist.k1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    c = harness.cell("femnist.k1", root)
    assert c["config"]["clients"] == 355
    assert c["traffic"]["inner_steps"] == 1
    assert c["limits"] == {"update1_gap": 0.01}
    reader = {m["name"]: m["reader"] for m in c["per_layer"]}["rounds_traced"]
    assert harness.load_module(reader).read({"rounds": 7}) == 7.0
    # the new metric lists only the new cell
    assert "rounds_traced" not in {m["name"] for m in harness.cell("femnist.full", root)["per_layer"]}


def test_unknown_workload_raises():
    with pytest.raises(KeyError):
        harness.cell("no.such.cell")


# -- the device check --------------------------------------------------------------

PEAKS = harness.load_json(harness.HERE / "peaks.json")


def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_device_check_refuses_the_cpu():
    import jax

    with pytest.raises(harness.NoChip):
        harness.check_devices(jax.devices("cpu"), 1, PEAKS)
    with pytest.raises(harness.NoChip):
        harness.check_devices([_dev("cpu", "cpu")], 1, PEAKS)
    with pytest.raises(harness.NoChip):
        harness.check_devices([], 1, PEAKS)


def test_device_check_counts_chips_and_knows_its_peaks():
    v5e = _dev("tpu", "TPU v5 lite")
    assert harness.check_devices([v5e], 1, PEAKS)["hbm_bytes_per_s"] == 819e9
    assert harness.check_devices([v5e] * 4, 4, PEAKS)["bf16_flops"] == 197e12
    with pytest.raises(harness.NoChip):
        harness.check_devices([v5e], 4, PEAKS)
    with pytest.raises(KeyError):
        harness.check_devices([_dev("tpu", "TPU v9 imaginary")], 1, PEAKS)


def test_run_refuses_the_cpu_and_prints_nothing(capsys):
    args = types.SimpleNamespace(workload="femnist.full", seed=1, seconds=1.0,
                                 trace=0)
    assert harness.main(args, 0.0) != 0
    assert capsys.readouterr().out == ""


# -- work counts ------------------------------------------------------------------

def test_lm_counts_by_hand():
    cfg = {"d_model": 8, "n_heads": 2, "head_dim": 4, "d_ff": 16,
           "vocab_size": 10, "n_layers": 3}
    # embedding 10*8; per layer q,k,v 8*2*4 each, o 2*4*8, MLP 3*8*16
    per_layer = 3 * 64 + 64 + 3 * 128
    assert counts.lm_params(cfg) == 80 + 3 * per_layer
    # 2 sequences of 5 tokens: 6 N T plus 6 S^2 (H hd) L per sequence
    want = 6 * (80 + 3 * per_layer) * 10 + 2 * 6 * 25 * 8 * 3
    assert counts.lm_flops(cfg, 2, 5) == want


def test_olmo_1b_counts_match_the_paper_size():
    cfg = harness.load_json(harness.HERE / "configs" / "olmo-1b.json")
    full = dict(cfg, n_layers=cfg["published_n_layers"])
    assert abs(counts.lm_params(full) - 1.177e9) / 1.177e9 < 0.01  # OLMo-1B: 1.18 B


def test_mlr_and_kernel_bytes_by_hand():
    cfg = {"n_features": 3, "n_classes": 2}
    assert counts.mlr_params(cfg) == 8
    assert counts.mlr_flops(cfg, 5) == 2 * (2 * 3 * 2 * 5)
    # m=2 rows of 10 f32: x, g, lam in and x out (4 * 2 rows) + x_s once
    assert counts.fused_update_bytes(2, 10, 4) == (8 + 1) * 40
    # xbar, lam in and u out (3 * 2 rows) + x_s once
    assert counts.round_tail_bytes(2, 10, 4) == (6 + 1) * 40


# -- trace reduction --------------------------------------------------------------

def test_reduce_on_hand_written_events():
    ms = 1_000_000
    tr = {"devices": {
        "TPU:0": [["a", 0, 2 * ms, {}], ["b", 1 * ms, 3 * ms, {}],
                  ["a", 6 * ms, 1 * ms, {}], ["c", 9 * ms, 3 * ms, {}]],
        "TPU:1": [["a", 0, 5 * ms, {}]]},
        "spans": [["chipbench/round", 0, 10 * ms], ["chipbench/fetch", 4 * ms, 2 * ms],
                  ["chipbench/dispatch", 7 * ms, 3 * ms]]}
    red = trace.reduce(tr)
    assert red["window_s"] == pytest.approx(0.010)
    # TPU:0 busy [0,4] + [6,7] + [9,10] = 6 ms, TPU:1 5 ms: mean 5.5 ms
    assert red["busy_s"] == pytest.approx(0.0055)
    assert red["idle_share"] == pytest.approx(0.45)
    ops = dict(red["op_seconds"])
    assert ops["a"] == pytest.approx((3 + 5) / 2 * 1e-3)
    assert ops["c"] == pytest.approx(0.5e-3)  # clipped to the window
    # TPU:1 idles over [5, 10] ms, its middle inside the dispatch span
    assert red["idle_gaps"][0] == ["chipbench/dispatch", pytest.approx(0.005)]
    assert red["idle_gaps"][1] == ["chipbench/fetch", pytest.approx(0.002)]
    assert red["idle_gaps"][2] == ["chipbench/dispatch", pytest.approx(0.002)]
    a = lambda name, stats: name == "a"  # noqa: E731
    assert trace.device_seconds(tr, a, (0, 10 * ms)) == pytest.approx(0.004)
    assert trace.count_ops(tr, a, (0, 10 * ms)) == 1.5


def _cpu_ops(process, thread):
    return "cpu" if process == "/host:CPU" and thread.startswith("tf_XLAPjRtCpuClient") else None


def test_recorded_trace_reduces_to_its_timeline():
    """Three rounds recorded on the CPU, each one jitted call and a 20 ms
    sleep inside the fetch span."""
    tr = trace.load(str(TESTDATA / "cpu_rounds.trace.json.gz"), _cpu_ops)
    assert [s[0] for s in tr["spans"]].count("chipbench/round") == 3
    red = trace.reduce(tr)
    t0, t1 = trace.window_of(tr["spans"])
    # an independent count of busy microseconds on a 1 us grid
    grid = np.zeros(int((t1 - t0) // 1000) + 1, bool)
    for _, s, d, _ in tr["devices"]["cpu"]:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            grid[int((a - t0) // 1000):int(np.ceil((b - t0) / 1000))] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    assert red["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    assert 0.75 < red["idle_share"] < 0.9
    # the three sleeps are the three longest gaps, inside the fetch spans
    assert [g[0] for g in red["idle_gaps"][:3]] == ["chipbench/fetch"] * 3
    assert all(0.019 < g[1] < 0.035 for g in red["idle_gaps"][:3])
    names = dict(red["device_ops"])
    assert {"dot_general.2", "dot_general.3"} <= set(names)
