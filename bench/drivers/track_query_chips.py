"""Track queries against a finished archive, served from every chip of
one host.

The same closed loop of analyst clients as ``track_query`` (whose
driver this one extends and leaves as it is), with the read path's
unit decodes spread over the cell's chips: set-up gives the program
the first ``chips`` devices (``analysis.configure_read_devices``), and
after ``track_query``'s own warm-up decodes every unit of the archive
once on every chip (``analysis.warm_read_path``), so that no chip
builds a program inside the window; every unit must decode to the
same bytes on every chip there (``units_differ_across_chips``, limit
0).  Each unit a client leads goes to the chip with the fewest unit
decodes under way.

The archive is the one of the configuration named by ``archive_of``
(same field, compression, tiling and engine, checked here), kept under
that configuration's name and key, so a checkout builds it and its
reference tracks once for both cells.  The comparison with the plain
reference is ``track_query``'s.
"""
from __future__ import annotations

import os

from bench import common, harness
from bench.drivers import track_query

# what the archive and its reference tracks are made from
ARCHIVE_KEYS = ("field", "frames", "field_seed", "frames_dtype",
                "compression", "tiling", "engine", "analysis")


class Driver(track_query.Driver):
    def _archive_dir(self):
        """``track_query``'s archive directory for the configuration
        named by ``archive_of``, once both files are seen to define
        the same archive."""
        name = self.cfg_file["archive_of"]
        entries = {c["name"]: c for c in harness.load_spec()["configs"]}
        if name not in entries:
            raise harness.BenchError(f"archive_of names {name!r}, which "
                                     f"BENCHMARK.json does not list")
        entry = entries[name]
        mine = harness.load_json(os.path.join(
            harness.ROOT, self.run.config_entry["file"]))
        theirs = harness.load_json(os.path.join(harness.ROOT,
                                                entry["file"]))
        differ = [k for k in ARCHIVE_KEYS if mine.get(k) != theirs.get(k)]
        if differ:
            raise harness.BenchError(
                f"{mine['name']} and {name} define different archives "
                f"({', '.join(differ)}), so they cannot share one")
        key = common.tree_digest(
            os.path.join(harness.ROOT, "src", "repro"),
            os.path.join(harness.ROOT, entry["file"]),
            os.path.join(harness.BENCH, "reference.py"),
            os.path.join(harness.BENCH, "fields"))
        return os.path.join(harness.CACHE, "archive", f"{name}-{key}")

    def setup(self):
        import jax

        from repro import analysis

        if not hasattr(analysis, "configure_read_devices"):
            raise harness.BenchError(
                "the program cannot place unit decodes on chips "
                "(no analysis.configure_read_devices)")
        analysis.configure_read_devices(
            jax.devices()[: self.run.cell["chips"]])
        super().setup()
        self.differ = analysis.warm_read_path(self.path)

    def check(self) -> dict:
        checks = super().check()
        # units whose set-up decodes were not the same bytes on every chip
        checks["units_differ_across_chips"] = (len(self.differ), 0)
        return checks

    def release(self):
        from repro import analysis

        super().release()
        analysis.configure_read_devices(None)
