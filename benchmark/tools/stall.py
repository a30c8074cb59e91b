#!/usr/bin/env python3
"""Freeze the process that holds the chip, as a stalled host would: every
``--every`` seconds, SIGSTOP for ``--stop-ms`` to each process that has libtpu
mapped (all its threads, the runtime's own among them), then SIGCONT. Started
beside a run of a train cell, it shows how long a freeze the cell's
``steps_in_flight`` hides (the run's ``step_done_s`` line shows each gap).
Ends by itself after ``--for`` seconds.

    python3 benchmark/tools/stall.py --after 20 --every 4 --stop-ms 1200 --for 70 &
"""

import argparse
import os
import signal
import time


def holders():
    me = os.getpid()
    for pid in filter(str.isdigit, os.listdir("/proc")):
        if int(pid) == me:
            continue
        try:
            with open(f"/proc/{pid}/maps") as f:
                if "libtpu" in f.read():
                    yield int(pid)
        except OSError:
            pass


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--after", type=float, default=20.0)
    p.add_argument("--every", type=float, default=4.0)
    p.add_argument("--stop-ms", type=float, default=1200.0)
    p.add_argument("--for", dest="total", type=float, default=70.0)
    a = p.parse_args()
    end = time.monotonic() + a.total
    time.sleep(a.after)
    while time.monotonic() < end:
        pids = list(holders())
        t = time.time()
        try:
            for pid in pids:
                os.kill(pid, signal.SIGSTOP)
            time.sleep(a.stop_ms / 1e3)
        finally:
            for pid in pids:
                try:
                    os.kill(pid, signal.SIGCONT)
                except OSError:
                    pass
        print(f"stall: {pids} stopped {a.stop_ms:.0f} ms at {t:.3f}", flush=True)
        time.sleep(a.every)


if __name__ == "__main__":
    main()
