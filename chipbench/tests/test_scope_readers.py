"""The readers of the program's name scopes on events written by hand.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import pytest

from chipbench import harness, trace

MS = 1_000_000
J = "jit(one_round)"
LOOP = f"{J}/round.inner_loop/while"
BODY = f"{LOOP}/body/closed_call"


def _read(metric, tr, rounds=2):
    ctx = {"trace": tr, "reduced": trace.reduce(tr), "window": trace.window_of(tr["spans"]),
           "rounds": rounds}
    return harness.load_module(harness.HERE / "metrics" / f"{metric}.py").read(ctx)


def _trace(ops):
    """One device; two rounds over [0, 20] ms."""
    return {"devices": {"TPU:0": ops},
            "spans": [["chipbench/round", 0, 10 * MS], ["chipbench/round", 10 * MS, 10 * MS]]}


def _op(name, start, dur, tf_op=None, category="data formatting"):
    args = {"hlo_category": category}
    if tf_op is not None:
        args["tf_op"] = tf_op
    return [name, start * MS, dur * MS, args]


SCOPED = _trace([
    _op("while.1", 0, 16, LOOP, "while"),
    _op("fusion.1", 0, 3, f"{BODY}/round.client_grad/dot_general:"),
    _op("fusion.2", 3, 1, f"{BODY}/round.client_grad/arena_pack/jit(_pad)/pad:"),
    _op("pad.1", 4, 2, f"{BODY}/round.client_update/fused_update_arena/relayout/jit(_pad)/pad:"),
    _op("closed_call.3", 6, 2, f"{BODY}/round.client_update/fused_update_arena/pallas_call:",
        "tpu_custom_call"),
    _op("copy.1", 8, 4, f"{LOOP}:"),
    _op("reshape.1", 12, 1, f"{J}/round.uplink/round_tail/relayout/reshape:"),
    _op("slice.1", 13, 2, f"{J}/dynamic_slice:"),  # the benchmark's batch slicing
    _op("copy.2", 15, 1),  # no name stack at all
    _op("fusion.9", 19, 3, f"{J}/round.metrics/reduce_sum:"),  # ends past the window
])


def test_relayout_ms_reads_the_relayout_scope():
    # the tiling pad (2 ms) and the uplink reshape (1 ms) over 2 rounds
    assert _read("relayout_ms", SCOPED) == pytest.approx(1.5)


def test_client_grad_scope_ms_reads_the_gradient_scope():
    # the gradient fusion and its arena pack (3 + 1 ms), the while left out
    assert _read("client_grad_scope_ms", SCOPED) == pytest.approx(2.0)
    assert _read("client_grad_scope_ms", SCOPED, rounds=1) == pytest.approx(4.0)


def test_unscoped_share_reads_what_no_phase_holds():
    # busy [0, 16] + [19, 20] = 17 ms; the slice (2 ms) and the nameless
    # copy (1 ms) run under no round phase
    assert _read("unscoped_share", SCOPED) == pytest.approx(100.0 * 3 / 17)


@pytest.mark.parametrize("metric", ["relayout_ms", "client_grad_scope_ms", "unscoped_share"])
def test_a_program_without_phase_scopes_gives_no_reading(metric):
    # as the program read before its phases were named: name stacks, none a phase
    plain = _trace([_op("while.1", 0, 10, f"{J}/while", "while"),
                    _op("pad.1", 0, 4, f"{J}/while/body/closed_call/jit(_pad)/pad:"),
                    _op("fusion.1", 4, 4, f"{J}/while/body/jvp(loss)/dot_general:")])
    assert _read(metric, plain) is None
    # and a trace with no name stacks at all
    bare = _trace([_op("fusion.1", 0, 4)])
    assert _read(metric, bare) is None
