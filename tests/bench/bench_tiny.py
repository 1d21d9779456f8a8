"""A copy of the benchmark's data files with the configurations cut to a size
the CPU runs in seconds, for the benchmark's tests."""

import json
import shutil
from pathlib import Path

from bench.spec import ROOT

# At these sizes the climate and read limits of the chip still separate sound
# runs from the control on the CPU (climate n=512: program gaps up to
# 2.0e-5, control from 3.4e-4; reads: up to 4.7e-7 and from 7.4e-6).  The
# GMM graph is well conditioned when small, so both read lower there
# (n=64: program up to 1.6e-6, control from 1.3e-5): its tiny copy gets a
# limit set from those readings by the same rule.
TINY = {
    "climate-128x128": {"n_lat": 16, "n_lon": 32, "n": 512},
    "climate-360x720": {"n_lat": 16, "n_lon": 32, "n": 512, "panel_rows": 128},
    "synth-gmm-22528": {"n": 64, "limits": {"write": {"answer_gap": 5e-6}}},
}


def tiny_root(tmp: Path) -> Path:
    (tmp / "bench").mkdir(parents=True)
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub)
    for name, sizes in TINY.items():
        path = tmp / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(sizes)
        path.write_text(json.dumps(cfg))
    return tmp
