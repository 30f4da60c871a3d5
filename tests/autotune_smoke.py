"""No-accelerator autotune smoke (CI numpy leg).

End to end with REPRO_BACKEND=numpy and no calibration table on disk:
calibrate from obs spans, search the plan space, compress with the
chosen plan via ``compress(..., autotune=True)``, byte-diff the result
against the SAME plan configured by hand, and render the explain()
report.  Real raises, not asserts: the smoke must fail under -O too.

    PYTHONPATH=src python tests/autotune_smoke.py
"""
import os
import tempfile

import numpy as np

from repro import autotune
from repro.core import CompressionConfig, compress, decompress
from repro.core import tiling


def main():
    rng = np.random.default_rng(11)
    base = np.cumsum(rng.normal(size=(6, 32, 32)).astype(np.float32),
                     axis=0)
    u, v = base, base[::-1].copy()

    with tempfile.TemporaryDirectory() as td:
        # calibrate on the numpy backend only (the leg has no
        # accelerator; xla would only add compile time to the smoke)
        path = os.path.join(td, "calib.json")
        table = autotune.calibrate(backends=("numpy",), path=path)
        if not table.coeffs:
            raise SystemExit("calibration fitted no coefficients")
        reloaded = autotune.load_table(path)
        if reloaded.coeffs != table.coeffs:
            raise SystemExit("calibration table did not roundtrip")

        cfg = CompressionConfig(eb=1e-2, track_index=False,
                                backend="numpy")
        tuned = autotune.tune_config(u, v, cfg, table=reloaded)
        blob_auto, stats = compress(u, v, tuned)

        # byte-identity: the autotuned container must equal the same
        # plan run by hand (autotuning changes speed, never bytes)
        if tuned.tiling is None:
            blob_hand, _ = compress(u, v, tuned)
        else:
            blob_hand, _ = tiling.compress_tiled(u, v, tuned, tuned.tiling)
        if blob_auto != blob_hand:
            raise SystemExit("autotuned container diverged from the "
                             "hand-configured plan")
        ur, vr = decompress(blob_auto)
        if abs(ur.astype("float64") - u).max() > stats["eb_abs"]:
            raise SystemExit("autotuned container violated the bound")

        report = autotune.explain()
        if "chosen" not in report:
            raise SystemExit("explain() produced no chosen plan")
        print(report)

        # streaming entry point: autotune=True picks grid + engine.
        # Pre-seed the default table location so the stream tune loads
        # it instead of recalibrating from scratch mid-smoke.
        autotune.save_table(table)
        frames = [(u[t], v[t]) for t in range(u.shape[0])]
        blob_s, _ = tiling.compress_stream(frames, cfg, autotune=True)
        if not blob_s:
            raise SystemExit("autotuned stream produced no container")

    print("autotune smoke ok: chose", autotune.last_report()["chosen"])


if __name__ == "__main__":
    main()
