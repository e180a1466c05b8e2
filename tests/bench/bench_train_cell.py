"""The train cell's entries, for tests: ``phi4mini.train_tp4`` is built and
was run on the chip, but is not yet a cell of ``BENCHMARK.json`` (PERF.md,
Open questions); tests load it from a benchmark file of their own."""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ROOT / "benchmarks" / "chip" / "configs"

ENTRIES = {
    "configs": [
        {"name": "phi4-mini-3.8b-tp4", "file": str(CONFIGS / "phi4-mini-3.8b-tp4.json")},
    ],
    "workloads": [
        {"name": "phi4mini.train_tp4", "config": "phi4-mini-3.8b-tp4",
         "traffic": "pretrain_8x1024", "chips": 4},
    ],
    "end_to_end": [
        {"name": "train_tokens_per_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [
        {"name": "mfu.train", "unit": "%"},
        {"name": "collective_share.train", "unit": "%"},
        {"name": "device_idle_share.train", "unit": "%"},
    ],
}


def load(directory):
    """The train cell, through ``harness.load_cell``, from ``directory``."""
    import harness

    path = Path(directory) / "BENCHMARK.json"
    path.write_text(json.dumps(ENTRIES))
    return harness.load_cell("phi4mini.train_tp4", bench_path=path)
