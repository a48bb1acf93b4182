"""Train-while-serve demo (ISSUE 7): checkpoint hot-swap end to end.

The trainer (``launch.train.run``) writes full-state checkpoint anchors
every round while a server thread of the SAME process answers query
batches from that directory via the hot-swap watcher
(``launch.serve.run_watch``): the server picks up each new anchor between
query batches, and a deliberately truncated checkpoint file is REJECTED
loudly while serving continues from the last good step.  One process holds
the device, so the demo runs on a single accelerator as it does on the CPU.

    PYTHONPATH=src python examples/serve_demo.py

Phases:
  1. train rounds 0..3 and stop (anchors step_1..3 on disk);
  2. plant a truncated file at a far-future step -- the newest file in the
     directory is now garbage, which is exactly the case ``latest_step``
     alone cannot survive;
  3. start the hot-swap server in a thread: it must reject the planted file
     and serve step 3;
  4. resume the trainer to round 6 while the server keeps answering queries
     -- the served step must advance as new anchors land.

The batched static-serving demo (prefill + per-arch decode cache) stays at
the end.
"""
import pathlib
import tempfile
import threading
import time

from repro import checkpoint as ckpt
from repro.launch.serve import run as serve_once
from repro.launch.serve import run_watch
from repro.launch.train import run as train_run


def train(ckpt_dir: str, steps: int, *, resume: bool = False):
    train_run("olmo-1b", steps=steps, k=1, eta=0.05, m=2, per_client_batch=2,
              seq_len=32, log_every=1, ckpt_dir=ckpt_dir, ckpt_every=1,
              resume=resume)


with tempfile.TemporaryDirectory() as d:
    print("=== phase 1: train rounds 0..3 ===", flush=True)
    train(d, 3)
    assert ckpt.steps(d), "trainer wrote no anchors"

    print("=== phase 2: plant a truncated checkpoint at the newest step ===",
          flush=True)
    fake = pathlib.Path(d) / "step_99999999.msgpack"
    fake.write_bytes(b"\x00" * 37)  # unreadable msgpack, newest by name

    print("=== phase 3+4: serve while the trainer resumes to round 6 ===",
          flush=True)
    rows: list = []
    stop = threading.Event()
    out: dict = {}

    def serve_loop():
        out["history"], out["watcher"] = run_watch(
            "olmo-1b", ckpt_dir=d, batch=2, prompt_len=16, new_tokens=2,
            poll_interval=0.2, duration=600.0, wait_first=30.0,
            stop_when=stop.is_set, history=rows)

    th = threading.Thread(target=serve_loop)
    th.start()
    try:
        t0 = time.time()
        while not rows:  # server up and answering before the trainer resumes
            assert th.is_alive(), "serve thread died before the first query"
            assert time.time() - t0 < 120, "server never answered a query"
            time.sleep(0.2)
        first_step = rows[0]["step"]

        train(d, 6, resume=True)
        t0 = time.time()
        while rows[-1]["step"] < 6 and time.time() - t0 < 30:
            time.sleep(0.2)  # grace: let the watcher poll the final anchor
    finally:
        stop.set()
        th.join(timeout=120)
    assert not th.is_alive(), "serve thread failed to stop"

    history, watcher = out["history"], out["watcher"]
    served = sorted({row["step"] for row in history})
    rounds = sorted({row["round"] for row in history})
    print(f"[demo] served steps {served}, rounds {rounds}, "
          f"swaps={watcher.swaps} rejected={watcher.failures}")
    assert watcher.failures >= 1, "truncated checkpoint was never rejected"
    assert 99999999 in watcher.bad, "the planted file was not the reject"
    assert first_step <= 3, f"first served step {first_step} not from phase 1"
    assert len(served) >= 2, f"served step never advanced: {served}"
    assert max(rounds) > min(rounds), f"served round never advanced: {rounds}"
    steps_seq = [row["step"] for row in history]
    assert steps_seq == sorted(steps_seq), "served step went backwards"
    print("[demo] hot-swap serving OK: truncated anchor rejected, "
          "served round advanced with training")

print("\n=== static batched serving (per-arch decode caches) ===")
for arch in ["olmo-1b", "rwkv6-1.6b"]:
    print(f"\n=== {arch} (reduced config) ===")
    serve_once(arch, reduced=True, batch=4, prompt_len=32, new_tokens=8)
