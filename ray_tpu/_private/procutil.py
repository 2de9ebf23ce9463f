"""Child-process teardown shared by the head Node and follower NodeAgent."""

from __future__ import annotations

import subprocess
import sys
import time


def drain_procs(procs, deadline_s: float = 3.0, reap_timeout_s: float = 2.0):
    """Wait for `procs` to exit within a shared deadline, SIGKILL the rest,
    then reap the killed stragglers until each is dead, and say which were
    killed. The reap matters: SIGKILL is async, and a worker mid-boot that
    outlives the store teardown that follows would recreate the
    just-unlinked arena segment; a chip worker that is releasing its device
    memory when the signal lands is not gone `reap_timeout_s` later, and the
    chip is not free for the next process until it is. Every process in
    `procs` has a return code when this returns. Kill-all-then-reap keeps the
    usual worst case one reap round-trip, not `reap_timeout_s` per straggler."""
    deadline = time.monotonic() + deadline_s
    stragglers = []
    for p in procs:
        try:
            p.wait(timeout=max(0.05, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            stragglers.append(p)
    for p in stragglers:
        killed = time.monotonic()
        while True:
            try:
                p.wait(timeout=reap_timeout_s)
                break
            except subprocess.TimeoutExpired:
                print(f"ray_tpu: worker {p.pid} is still there "
                      f"{time.monotonic() - killed:.0f} s after SIGKILL; waiting for it",
                      file=sys.stderr, flush=True)
        print(f"ray_tpu: worker {p.pid} had not exited {deadline_s:.0f} s after the "
              f"shutdown and was killed (gone {time.monotonic() - killed:.1f} s later)",
              file=sys.stderr, flush=True)
