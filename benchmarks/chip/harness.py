"""What every cell shares: finding its files, the chip, the metric readers and
the result line.

A cell is found by name in ``BENCHMARK.json``. Its configuration file
(``configs/<config>.json``) holds the published config as run, at its top
level and under its published keys, beside the harness's own keys
(:data:`FILE_KEYS`): the repo's configuration (``repo_config``), the plain
reference (``refs/<reference>.py``), the driver that runs it
(``drivers/<entry>.py``) and the counts of its operations and bytes
(``<counts>.py``, default ``counts.py``). Its mix is
``traffic/<traffic>.json``; each metric is read by ``metrics/<metric>.py``.
So a new cell, mix, configuration or metric is new files and new entries,
and no edit here.

Every published key is placed, or loading fails and names it: it sets a
``ModelConfig`` field (:data:`CONFIG_FIELDS`, or the file's own ``fields``
map), is compared with what the repo's model runs (:data:`RUNS`: every key
that changes the arithmetic, such as biases, a window or the router's rule),
or is listed under ``unmapped`` with the reason it is not placed (names and
limits). A key that the file's ``fields`` map places is set and not judged,
``RUNS`` keys among them: a model whose router or RoPE the program states in
a field of its own comes in as a file, with no edit here. A field the repo's
configuration lacks fails loading and names the key. ``repo_fields`` sets
fields that no published key states (the share of experts held, KV
padding); each is listed in ``reduced`` (reason in ``departures``) or in
``assumed`` (reason in ``assumed_why``). A JSON list is set as a tuple.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# a configuration file's own keys; every other key is the published config
FILE_KEYS = frozenset({
    "name", "source", "paper", "repo_config", "reference", "entry", "counts", "deployment",
    "reduced", "departures", "assumed", "assumed_why", "fields", "repo_fields", "unmapped",
    "check",
})

# published config key -> the repo's ModelConfig field it sets, where the
# published config has the key
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
    # mixture of experts, in the DeepSeek and HF-MoE conventions
    "n_routed_experts": "num_experts",
    "num_local_experts": "num_experts",
    "num_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "n_shared_experts": "num_shared_experts",
    "moe_intermediate_size": "moe_d_ff",
    "first_k_dense_replace": "first_dense_layers",
    # latent attention (MLA)
    **{k: k for k in (
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    )},
}


def _window(cfg, pub):
    """The repo's window; none (None) runs any published window that no
    position the model takes reaches."""
    w = pub.get("sliding_window")
    if cfg.window is None and w is not None and w >= pub.get("max_position_embeddings", w + 1):
        return w
    return cfg.window


# published keys that change the model's arithmetic, compared with what the
# repo's model runs given its configuration and the published config, rather
# than set; ``head_dim`` is set by ``model_config``
RUNS = {
    "hidden_act": lambda cfg, pub: cfg.act,
    "partial_rotary_factor": lambda cfg, pub: 1.0,  # RoPE on the whole head
    "rope_scaling": lambda cfg, pub: None,  # plain RoPE at rope_theta
    "torch_dtype": lambda cfg, pub: cfg.dtype,
    "attention_bias": lambda cfg, pub: cfg.qkv_bias,
    "mlp_bias": lambda cfg, pub: False,  # no MLP or expert has biases
    "lm_head_bias": lambda cfg, pub: False,
    "sliding_window": _window,
    # the router: softmax over every expert, top-k, renormalised, unscaled
    "scoring_func": lambda cfg, pub: "softmax",
    "topk_method": lambda cfg, pub: "greedy",
    "n_group": lambda cfg, pub: pub.get("topk_group"),  # a token reaches every group
    "topk_group": lambda cfg, pub: pub.get("n_group"),
    "norm_topk_prob": lambda cfg, pub: True,
    "routed_scaling_factor": lambda cfg, pub: 1.0,
    "moe_layer_freq": lambda cfg, pub: 1,  # experts in every layer after the dense ones
    "seq_aux": lambda cfg, pub: False,  # the balancing loss is over the batch
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
        """The published config as run: every key of the file but its own."""
        return {k: v for k, v in self.config.items() if k not in FILE_KEYS}

    def reference(self):
        return load_module(HERE / "refs" / f"{self.config['reference']}.py")

    def driver(self):
        return load_module(HERE / "drivers" / f"{self.config['entry']}.py")

    def dims(self):
        """Operations and bytes of the model as run, from the counts module
        the file names under ``counts`` (``counts.py``, a dense GQA decoder,
        where it names none), given the published keys and the file's
        ``repo_fields`` (the experts held, where a chip holds a share)."""
        name = self.config.get("counts", "counts")
        if name != "counts" and not name.startswith("counts_"):
            raise ValueError(f"counts module {name!r}: the name is 'counts' or 'counts_<family>'")
        return importlib.import_module(name).Dims.from_published(
            self.published, self.config.get("repo_fields", {})
        )

    def model_config(self):
        """The repo's configuration with the published sizes as run."""
        conf, pub = self.config, self.published
        cfg = repo_config(conf["repo_config"])
        mapped = conf.get("fields", {})
        fields = {**CONFIG_FIELDS, **mapped}
        unmapped = conf.get("unmapped", {})
        unplaced = sorted(
            k for k in pub
            if k not in fields and k not in RUNS and k != "head_dim" and not unmapped.get(k)
        )
        if unplaced:
            raise ValueError(
                f"{conf['name']}: published keys {unplaced} are neither set, nor checked, nor "
                "listed under 'unmapped' with a reason"
            )
        have = {f.name for f in dataclasses.fields(cfg)}
        sets: dict = {}
        for k, f in fields.items():
            if k in pub:
                if f not in have:
                    raise ValueError(
                        f"{conf['name']}: published key {k!r} is mapped to {f!r}, which the "
                        f"repo's configuration {type(cfg).__name__} has no field for"
                    )
                if sets.get(f, pub[k]) != pub[k]:
                    raise ValueError(f"{conf['name']}: {f} is {sets[f]!r} and {pub[k]!r}")
                sets[f] = pub[k]
        if pub.get("head_dim"):
            sets["head_dim"] = pub["head_dim"]
        elif cfg.attention != "mla":
            sets["head_dim"] = pub["hidden_size"] // pub["num_attention_heads"]
        repo = conf.get("repo_fields", {})
        reasons = {
            **{k: conf.get("departures", {}).get(k) for k in conf.get("reduced", [])},
            **{k: conf.get("assumed_why", {}).get(k) for k in conf.get("assumed", {})},
        }
        unexplained = sorted(f for f in repo if not reasons.get(f) or f in sets)
        if unexplained:
            raise ValueError(
                f"{conf['name']}: repo_fields {unexplained} are set by a published key, or not "
                "listed in 'reduced' (reason in 'departures') or 'assumed' (in 'assumed_why')"
            )
        cfg = cfg.replace(**{f: _tupled(v) for f, v in {**sets, **repo}.items()})
        for k, v in conf.get("assumed", {}).items():
            v = _tupled(v)
            if getattr(cfg, k, v) != v:
                raise ValueError(f"{cfg.name}: {k} is {getattr(cfg, k)}, the file assumes {v}")
        runs = {k: f(cfg, pub) for k, f in RUNS.items() if k not in mapped}
        off = {k: (pub[k], v) for k, v in runs.items() if pub.get(k, v) != v}
        if off:
            raise ValueError(
                f"{cfg.name}: the repo's model cannot run this published config "
                f"(published, runs): {off}"
            )
        return cfg


def _tupled(v):
    """A JSON list as the tuple a ``ModelConfig`` field holds, nested lists too."""
    return tuple(_tupled(x) for x in v) if isinstance(v, list) else v


def repo_config(name: str):
    """The repo's configuration ``name``; ``<arch>-reduced`` is that
    architecture's reduced variant, at a size the CPU holds."""
    from repro.configs import get_config, get_reduced

    if name.endswith("-reduced"):
        return get_reduced(name[: -len("-reduced")])
    return get_config(name)


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
