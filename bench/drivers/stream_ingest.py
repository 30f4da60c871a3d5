"""A simulation streaming frames in situ into the compressor.

The producer yields pre-generated frames as fast as ``compress_stream``
takes them, into a journaled file.  The frames are the configuration's
flow translated by a number of cells drawn from the seed: the same flow
met at another moment, so every seed brings the same work.  The stream
emits a window's units once the frames two windows later are in, and a
unit is durable once the journal checkpoint after it is fsynced; the
program's carrier counter ``journal.checkpoints`` marks that moment
(checkpoint k makes stream window k durable).

The measured window runs from one durable checkpoint c(s) to a later
one c(x), so the rate counts whole units: the latest start s at or
after checkpoint ``WARM_WINDOWS`` (the first middle window's emission
builds shapes the first window never uses) such that c(x) - c(s) lasts
``--seconds``.  The producer has to decide where to stop two windows
ahead of c(x): when it is asked for the frame after the one that seals
window w, the stream is starting the work that emits window w - 1, so
the gaps between those moments give the time one window takes.  Once
that cadence says (w - WARM_WINDOWS) windows last ``--seconds`` (plus
``MARGIN``), or another window would end past ``DEADLINE_S`` after the
process started, it stops feeding, waits for c(w) and ends the stream,
so no work is left running.

The comparison reads one stream window of the span, drawn from the
seed, with the frame before it (so the faces across the seam between
two stream windows are compared too), back from the file through the
program's salvage and region decoder, as a reader after a crash would,
and holds it to the configuration's guarantees with the plain
reference.
"""
from __future__ import annotations

import os
import shutil
import statistics
import threading
import time

import numpy as np

from bench import common, harness
from bench.readings import Readings

WARM_WINDOWS = 1      # checkpoints before the window may start
MARGIN = 0.1          # feed windows for this share past --seconds
DEADLINE_S = 320      # no window may end later after process start
POLL_S = 0.02         # how often the monitor reads the checkpoint counter


class _Stop(Exception):
    """Ends the stream once the window's last unit is durable."""


class Driver:
    def __init__(self, run):
        self.run = run
        self.conf = run.config
        self.mix = run.traffic
        self.attempted = self.failed = 0
        self.ckpts = []           # (perf_counter, obs snapshot) per checkpoint
        self.pulls = {}           # w -> when the producer reached window w
        self.x = None             # the last window fed
        self.error = None

    # -- stream plumbing -------------------------------------------------
    def _monitor(self):
        from repro import obs

        ck = obs.counter("journal.checkpoints")
        seen = ck.value
        while not self._done.is_set():
            c = ck.value
            if c != seen:
                now = time.perf_counter()
                snap = obs.snapshot()
                for _ in range(c - seen):
                    self.ckpts.append((now, snap))
                seen = c
                if len(self.ckpts) > WARM_WINDOWS:
                    self.run.begin_trace()
                self._tick.set()
            time.sleep(POLL_S)

    def _stop_after(self, w) -> bool:
        """Asked for the frame after the one that seals window w."""
        if len(self.pulls) < 2:
            return False
        ts = [self.pulls[k] for k in sorted(self.pulls)]
        period = statistics.median(b - a for a, b in zip(ts, ts[1:]))
        # c(w) comes about two periods after this moment
        late = (time.perf_counter() + 3 * period - self.run.t_start
                > DEADLINE_S)
        n = w - WARM_WINDOWS
        return n >= 1 and (late or n * period >= self.run.seconds * (
            1 + MARGIN))

    def _frames(self):
        wt = self.grid.window_t
        n = len(self.u)
        for i in range(n):
            if i > 1 and (i - 1) % wt == 0 and (i - 1) // wt >= 3:
                w = (i - 1) // wt - 3       # frame i - 1 sealed window w
                self.pulls[w] = time.perf_counter()
                if self._stop_after(w) or i + wt > n:
                    self.x = w
                    while len(self.ckpts) <= w and not self._failed.is_set():
                        self._tick.wait(0.05)
                        self._tick.clear()
                    raise _Stop()
            yield self.u[i], self.v[i]

    def _stream(self):
        from repro.core import compress_stream

        try:
            compress_stream(self._frames(), self.cfg, self.grid,
                            value_range=self.range, sink=self.path,
                            **self.conf.get("engine", {}))
        except _Stop:
            pass
        except BaseException as e:       # surfaces in window()
            self.error = e
        finally:
            self._failed.set()
            self._tick.set()

    # -- driver protocol ---------------------------------------------------
    def setup(self):
        run = self.run
        self.u, self.v = common.field(self.conf, self.mix["frames"],
                                      common.seed_shift(self.conf, run.seed))
        self.range = common.value_range(self.u, self.v)
        self.cfg, self.grid = common.program_config(self.conf)
        d = os.path.join(harness.CACHE, "ingest", run.cell["name"])
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
        self.path = os.path.join(d, "stream.cptt")
        self._done = threading.Event()
        self._tick = threading.Event()
        self._failed = threading.Event()
        self._mon = threading.Thread(target=self._monitor, daemon=True)
        self._thr = threading.Thread(target=self._stream, daemon=True)
        self._mon.start()
        self._thr.start()

    def window(self):
        run = self.run
        self._thr.join()
        self._done.set()
        self._mon.join()
        if self.error is not None:
            raise self.error
        x, first = self.x, WARM_WINDOWS
        if x is None or len(self.ckpts) <= x or x <= first:
            raise harness.BenchError(
                f"the stream ended at checkpoint {len(self.ckpts)} before "
                f"its window")
        t_end = self.ckpts[x][0]
        s = max([k for k in range(first, x)
                 if t_end - self.ckpts[k][0] >= run.seconds] or [first])
        run.window_start(self.ckpts[s][0], counters=self.ckpts[s][1])
        run.window_end(t_end, counters=self.ckpts[x][1])
        self.s = s
        H, W = self.u.shape[1:]
        self.tiles = -(-H // self.grid.tile_h) * -(-W // self.grid.tile_w)
        self.attempted = (x - s) * self.tiles
        self.notes = {
            "span": [s, x],
            "checkpoints_s": [t - run.t_start for t, _ in self.ckpts],
            "fed_s": {w: t - run.t_start
                      for w, t in sorted(self.pulls.items())}}

    def _units(self, ta, tb):
        from repro.core import encode

        return [e for e in encode.tiled_header(self.blob)["units"]
                if e["box"][0] >= ta and e["box"][1] <= tb]

    def end_to_end(self) -> dict:
        from repro.core import encode

        wt = self.grid.window_t
        ta, tb = (self.s + 1) * wt, (self.x + 1) * wt
        self.blob, _ = encode.salvage_container(self.path)
        units = self._units(ta, tb)
        self.failed = max(self.attempted - len(units), 0)
        in_bytes = (tb - ta) * self.u[0].nbytes * 2
        return {"encode_MBps": in_bytes / 1e6 / self.run.window_s,
                "compression_ratio":
                    in_bytes / max(sum(e["len"] for e in units), 1)}

    def release(self):
        pass

    def read_back(self, ta, tb):
        """Decoded (u, v) of frames [ta, tb) from the file, through the
        program's region decoder; None when the units are not there."""
        from repro.core import decompress_region, encode

        H, W = self.u.shape[1:]
        try:
            return decompress_region(self.blob, (ta, tb, 0, H, 0, W))
        except (encode.ContainerError, ValueError, KeyError) as e:
            self.notes["read_back_error"] = repr(e)
            return None

    def check(self) -> dict:
        wt = self.grid.window_t
        k = self.s + 1 + int(np.random.default_rng(self.run.seed).integers(
            self.x - self.s))
        ta, tb = k * wt - 1, (k + 1) * wt      # the seam frame too
        self.notes["checked_window"] = k
        checks = {"units_missing":
                  (self.tiles - len(self._units(ta + 1, tb)) + self.failed,
                   0)}
        got = self.read_back(ta, tb)
        if got is None:
            inf = float("inf")
            checks.update(max_err_over_eb=(inf, 1.0),
                          false_cases=(inf, 0), tracks_changed=(inf, 0))
            return checks
        lo, hi = self.range
        checks.update(common.compare_frames(
            self.conf, self.u[ta:tb], self.v[ta:tb], *got, lo, hi))
        return checks

    def readings(self, peaks):
        return Readings(self.run, peaks,
                        counts={"stream_windows": self.x - self.s})
