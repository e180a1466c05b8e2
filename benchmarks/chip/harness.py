"""What every cell shares: finding its files, the chip, the metric readers and
the result line.

A cell is found by name in ``BENCHMARK.json``. Its configuration file
(``configs/<config>.json``) names the repo's configuration, the published
config as run, the plain reference beside it (``refs/<reference>.py``) and the
driver that runs it (``drivers/<entry>.py``); its mix is
``traffic/<traffic>.json``; each metric is read by ``metrics/<metric>.py``.
So a new cell, mix, configuration or metric is new files and new entries,
and no edit here.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# published config key -> the repo's ModelConfig field it sets
CONFIG_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path, name: Optional[str] = None):
    spec = importlib.util.spec_from_file_location(name or f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    end_to_end: list  # BENCHMARK.json metric entries that this cell reports
    per_layer: list
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    t_start: float = 0.0  # perf_counter at process start
    device: dict = field(default_factory=dict)
    scratch: str = ""  # a temporary directory of the run's, removed when it ends

    @property
    def published(self) -> dict:
        return self.config["published"]

    def reference(self):
        return load_module(HERE / "refs" / f"{self.config['reference']}.py")

    def driver(self):
        return load_module(HERE / "drivers" / f"{self.config['entry']}.py")

    def dims(self):
        from counts import Dims

        return Dims.from_published(self.published)

    def model_config(self):
        """The repo's configuration with the published sizes as run."""
        from repro.configs import get_config

        cfg = get_config(self.config["repo_config"])
        pub = self.published
        cfg = cfg.replace(
            head_dim=pub.get("head_dim") or pub["hidden_size"] // pub["num_attention_heads"],
            **{f: pub[k] for k, f in CONFIG_FIELDS.items()},
        )
        for k, v in self.config.get("assumed", {}).items():
            if getattr(cfg, k, v) != v:
                raise ValueError(f"{cfg.name}: {k} is {getattr(cfg, k)}, the file assumes {v}")
        if cfg.act != pub["hidden_act"] or pub["partial_rotary_factor"] != 1.0:
            raise ValueError(f"{cfg.name}: the repo's model cannot run this published config")
        return cfg


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads((bench_path.parent / conf["file"]).read_text()),
        traffic=json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def device_info(chips: int) -> dict:
    """The chip as JAX reports it; raises :class:`NoChip` without a TPU."""
    import jax

    from peaks import peaks_for

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    kind = devs[0].device_kind
    peaks_for(kind)  # an unknown chip is an error, not a default
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


def read_metrics(entries: list, view: Any, required: bool) -> dict:
    """Run each metric's reader on the run's view; a reader that finds nothing
    returns None and its metric is left out (an error where ``required``)."""
    out = {}
    for m in entries:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py", f"metric_{len(out)}")
        value = reader.read(view)
        if value is None:
            if required:
                raise RuntimeError(f"metric {m['name']} found nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(result: dict, checks: list) -> None:
    """The run's last lines: each compared number beside its limit on stderr,
    and the result as the last line of stdout, with the checks last."""
    for name, value, limit in checks:
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    print(json.dumps(result), flush=True)
