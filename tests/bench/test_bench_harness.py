"""The benchmark's yardstick on the CPU: trace reduction, operation and byte
counts, traffic generation, configuration files, and the harness's refusals."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from typing import Any

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import counts  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402

DATA = Path(__file__).with_name("data")
PROBE = DATA / "tpu_probe.xplane.pb"
CONFIGS = BENCH / "configs"

# A hand-made trace of one chip: a fusion runs over [1000, 5000) ns and
# [8000, 10000), an all-reduce over [3000, 7000), inside one run of jit_step.
COLLECTIVE_TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 10000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2
    name: "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %b), replica_groups={{0,1,2,3}}" } }
  event_metadata { key: 10 value { id: 10 name: "jit_step(123)" } }
}
"""


# -- trace reduction ------------------------------------------------------------


def test_reduces_a_recorded_tpu_trace():
    """A trace recorded on a v5e: two jitted programs run three times each;
    host spans ``bench.mark:<i>`` sit on the same clock."""
    tr = trace_reduce.load(str(PROBE), host_prefixes=("bench.mark",))
    assert sorted(tr.devices) == [0]
    assert [h[0] for h in tr.host] == ["bench.mark:0", "bench.mark:1", "bench.mark:2"]
    dev = tr.devices[0]
    assert [p.name for p in dev.programs] == ["jit__lambda"] * 6
    lo, hi = dev.programs[0].start, dev.programs[-1].end
    assert [p.dur for p in dev.programs] == [39902, 142792, 39967, 142362, 40183, 141525]
    merged = trace_reduce.merge(((o.start, o.end) for o in dev.ops), lo, hi)
    assert trace_reduce.busy_ns(dev, lo, hi) == trace_reduce.total(merged) == 546662
    # busy lies inside the programs' own spans, and the gaps fill the rest
    assert trace_reduce.total(merged) <= sum(p.dur for p in dev.programs)
    gaps = trace_reduce.gaps(merged, lo, hi)
    assert trace_reduce.total(gaps) + trace_reduce.total(merged) == hi - lo
    assert max(e - s for s, e in gaps) == 3800695
    assert trace_reduce.collective_ns(dev, lo, hi) == (0, 0)
    top = trace_reduce.top_ops(dev, lo, hi, n=2)
    assert [name for name, _ in top] == ["jit__lambda/%sort.6", "jit__lambda/%fusion.13"]
    assert all(not name.endswith("while") for name, _ in trace_reduce.top_ops(dev, lo, hi))


def test_collective_time_and_its_exposed_part(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "coll.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(COLLECTIVE_TRACE))
    dev = trace_reduce.load(str(path)).devices[0]
    assert [(p.name, p.start, p.dur) for p in dev.programs] == [("jit_step", 1000, 10000)]
    assert [o.opcode for o in dev.ops] == ["fusion", "all-reduce", "fusion"]
    # the all-reduce runs 4000 ns, of which [5000, 7000) has no fusion beside it
    assert trace_reduce.collective_ns(dev, 1000, 11000) == (4000, 2000)
    assert trace_reduce.busy_ns(dev, 1000, 11000) == 8000
    merged = trace_reduce.merge(((o.start, o.end) for o in dev.ops), 1000, 11000)
    assert trace_reduce.gaps(merged, 1000, 11000) == [(7000, 8000), (10000, 11000)]


# Three runs of the train step on one chip, the first and last cut short by
# the trace's start and stop: ops over [1000, 2000), [2000, 3500), an
# all-reduce over [3000, 4500), [5000, 6000) and [6000, 6500).
TRAIN_TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 10 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 10 offset_ps: 5000000 duration_ps: 500000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1500000 }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2
    name: "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %b), replica_groups={{0,1,2,3}}" } }
  event_metadata { key: 10 value { id: 10 name: "jit_step_fn(123)" } }
}
"""


def test_train_trace_reads_the_whole_steps_alone(tmp_path):
    from jax.profiler import ProfileData

    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(TRAIN_TRACE)
    )
    train = harness.load_module(BENCH / "drivers" / "train.py")
    trace, breakdown = train._reduce(str(tmp_path))
    # the one whole step runs [2000, 6000); the cut ones at the edges are left out
    assert trace["steps"] == 1
    assert trace["window_s"] == trace["step_device_s"] == pytest.approx(4e-6)
    assert trace["busy0_s"] == pytest.approx(3.5e-6)  # [2000, 4500) and [5000, 6000)
    assert trace["exposed_collective_s"] == pytest.approx(1e-6)  # [3500, 4500)
    assert breakdown["idle_gaps"] == [["between steps (host)", pytest.approx(5e-7)]]


def test_interval_union_clips_and_merges():
    assert trace_reduce.merge([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == [(1, 4), (5, 10)]
    assert trace_reduce.gaps([(1, 4), (5, 10)], 0, 12) == [(0, 1), (4, 5), (10, 12)]


@pytest.mark.parametrize(
    "text, label, opcode",
    [
        ("%fusion.13 = bf16[512,1024]{1,0} fusion(bf16[512,1024]{1,0} %c), kind=kOutput",
         "%fusion.13", "fusion"),
        ("%while = (s32[]{:T(128)}, bf16[4]{0}) while((s32[]{:T(128)}, bf16[4]{0}) %t)",
         "%while", "while"),
        ("%all-gather-start.2 = (bf16[8]{0}, bf16[32]{0}) all-gather-start(bf16[8]{0} %p)",
         "%all-gather-start.2", "all-gather-start"),
    ],
)
def test_op_labels(text, label, opcode):
    assert trace_reduce.op_label(text) == (label, opcode)


def test_program_name_drops_the_fingerprint():
    assert trace_reduce.program_name("jit__ptick(1787815954494)") == "jit__ptick"


# -- operations and bytes ----------------------------------------------------------


def _file_cell(conf: dict) -> harness.Cell:
    return harness.Cell(
        name="probe", chips=1, config=conf, traffic={}, end_to_end=[], per_layer=[]
    )


def _conf(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _dsv2() -> dict:
    return json.loads((DATA / "deepseek-v2-shaped.json").read_text())


def _load(tmp_path, file: Path) -> harness.Cell:
    """The file's cell through ``load_cell``, from a BENCHMARK.json of its own."""
    bench = {
        "configs": [{"name": "probe", "file": str(file)}],
        "workloads": [{"name": "probe.chat", "config": "probe", "traffic": "chat", "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.load_cell("probe.chat", bench_path=tmp_path / "BENCHMARK.json")


def _phi4() -> counts.Dims:
    return _file_cell(_conf("phi4-mini-3.8b")).dims()


def test_phi4_mini_counts_by_hand():
    """Phi-4-mini unpadded: d 3072, 32 layers, 24/8 heads of 128, d_ff 8192,
    vocab 200,064, tied."""
    dm = _phi4()
    embed = 200_064 * 3_072  # 614,596,608
    attn = 3_072 * 24 * 128 * 2 + 2 * 3_072 * 8 * 128  # q, o; k, v: 25,165,824
    mlp = 3 * 3_072 * 8_192  # 75,497,472
    assert dm.embed_params == embed == 614_596_608
    assert dm.layer_params == attn + mlp == 100_663_296
    assert dm.non_embedding_params == 3_221_225_472
    assert dm.total_params == 3_835_822_080
    assert dm.matmul_params == dm.total_params  # tied: the head is the embedding
    assert dm.weight_bytes == 7_671_644_160
    assert dm.kv_bytes_per_token == 2 * 32 * 8 * 128 * 2 == 131_072
    # one decoded token seeing 300 keys: 2 N + 4 L H Dh c
    assert dm.decode_flops(300) == 2 * 3_835_822_080 + 4 * 32 * 24 * 128 * 300
    # a 256-token prefill: every layer on every token, the head once, causal pairs
    assert dm.prefill_flops(256) == (
        2 * 3_221_225_472 * 256 + 2 * embed + 4 * 32 * 24 * 128 * (256 * 257 / 2)
    )
    assert dm.train_flops_per_token(1024) == 6 * 3_835_822_080 + 3 * 4 * 32 * 24 * 128 * 512.5


def test_counts_match_the_repo_param_count():
    from repro.configs import get_config, param_count

    assert param_count(get_config("phi4-mini-3.8b"))["total"] == _phi4().total_params


# -- traffic ----------------------------------------------------------------------


def _chat() -> dict:
    return json.loads((BENCH / "traffic" / "chat.json").read_text())


def test_traffic_is_a_function_of_the_seed():
    mix = _chat()
    a = traffic.make_requests(mix, 5_000_000_123, 20.0, 200_064)
    b = traffic.make_requests(mix, 5_000_000_123, 20.0, 200_064)
    c = traffic.make_requests(mix, 5_000_000_124, 20.0, 200_064)
    key = lambda rs: [(r.due, r.max_new, r.prompt.tobytes()) for r in rs]  # noqa: E731
    assert key(a) == key(b)
    assert key(a) != key(c)
    # every seed replays the mix's one schedule of arrivals and sizes
    shape = lambda rs: [(r.due, len(r.prompt), r.max_new) for r in rs]  # noqa: E731
    assert shape(a) == shape(c)
    assert all(x.prompt.tobytes() != y.prompt.tobytes() for x, y in zip(a, c))
    other = traffic.make_requests(dict(mix, schedule_seed=1), 5_000_000_123, 20.0, 200_064)
    assert [len(r.prompt) for r in other] != [len(r.prompt) for r in a]
    assert sorted(len(r.prompt) for r in other) == sorted(len(r.prompt) for r in a)


def test_open_loop_arrivals_fill_the_window():
    mix = _chat()
    rs = traffic.make_requests(mix, 7, 40.0, 200_064)
    due = np.array([r.due for r in rs])
    assert len(rs) == round(mix["rate_per_s"] * 40.0)
    assert np.all(np.diff(due) > 0) and 0 < due[0] and due[-1] < 40.0
    lens = np.array([len(r.prompt) for r in rs])
    p = mix["prompt_len"]
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
    assert abs(np.median(lens) - p["median"]) <= 0.05 * p["median"]
    assert all(0 <= r.prompt.min() and r.prompt.max() < 200_064 for r in rs)


@pytest.mark.parametrize("arrivals", ["gamma", "backlog"])
def test_other_arrival_shapes(arrivals):
    mix = dict(_chat(), arrivals=arrivals, gamma_shape=0.5, requests=12)
    mix["shared_prefix"] = {"tokens": 16, "groups": 2}
    rs = traffic.make_requests(mix, 3, 10.0, 1000)
    if arrivals == "backlog":
        assert len(rs) == 12 and all(r.due == 0 for r in rs)
    else:
        g = np.diff([0.0] + [r.due for r in rs])
        assert g.std() > g.mean()  # burstier than Poisson
    prefixes = {r.prompt[:16].tobytes() for r in rs}
    assert len(prefixes) <= 2


# -- refusals ----------------------------------------------------------------------


def _run(cwd, extra_env=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "phi4mini.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_a_cpu_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's files alone."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_device_check_refuses_other_chips(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Fake()])
    with pytest.raises(peaks.UnknownDevice):
        harness.device_info(1)
    Fake.device_kind = "TPU v5 lite"
    assert harness.device_info(1) == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(harness.NoChip):
        harness.device_info(4)
    monkeypatch.setattr(jax, "devices", lambda: [type("Cpu", (), {"platform": "cpu"})()])
    with pytest.raises(harness.NoChip):
        harness.device_info(1)


def test_every_cell_resolves_to_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (BENCH / "drivers" / f"{cell.config['entry']}.py").exists()
        assert (BENCH / "refs" / f"{cell.config['reference']}.py").exists()
        for m in cell.end_to_end + cell.per_layer:
            assert (BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]
        assert cell.model_config().num_layers == cell.published["num_hidden_layers"]


# -- configuration files ------------------------------------------------------------

# What both Phi-4-mini files ran as before the harness placed every published
# key (the 32-layer file; the four-chip one differs in depth alone).
PHI4_MODEL_CONFIG = {
    "name": "phi4-mini-3.8b", "family": "dense", "num_layers": 32, "d_model": 3072,
    "num_heads": 24, "num_kv_heads": 8, "d_ff": 8192, "vocab_size": 200064, "head_dim": 128,
    "act": "silu", "qkv_bias": False, "tie_embeddings": True, "embed_scale": False,
    "norm": "rms", "norm_eps": 1e-05, "use_rope": True, "rope_theta": 10000.0,
    "max_seq_len": 4096, "attention": "gqa", "kv_pad_to": 16, "window": None,
    "global_layers": (), "q_lora_rank": 0, "kv_lora_rank": 0, "qk_nope_head_dim": 0,
    "qk_rope_head_dim": 0, "v_head_dim": 0, "num_experts": 0, "experts_per_token": 0,
    "num_shared_experts": 0, "moe_d_ff": 0, "first_dense_layers": 0, "capacity_factor": 1.25,
    "router_aux_loss": 0.001, "ssm_state": 0, "ssm_heads": 0, "ssm_head_dim": 64,
    "ssm_expand": 2, "ssm_chunk": 64, "conv_kernel": 4, "encoder_layers": 0,
    "encoder_seq": 1500, "vision_dim": 0, "num_image_tokens": 0, "sharding_rules": (),
    "dtype": "bfloat16", "remat": "full", "loss_chunk": 512, "use_kernels": False,
}
PHI4_DIMS = {
    "d": 3072, "layers": 32, "heads": 24, "kv_heads": 8, "head_dim": 128, "d_ff": 8192,
    "vocab": 200064, "tied": True, "bytes_per_param": 2,
}


def _default(f: dataclasses.Field):
    return f.default_factory() if f.default_factory is not dataclasses.MISSING else f.default


def _off_pin(cfg, pin: dict) -> dict:
    """Where ``cfg`` leaves ``pin``: a pinned key that is no field, a pinned
    value that moved, or a field outside the pin off its declared default."""
    fields = {f.name: f for f in dataclasses.fields(cfg)}
    want = {**{k: _default(f) for k, f in fields.items()}, **pin}
    got = {k: getattr(cfg, k) if k in fields else "no field" for k in want}
    return {k: (got[k], v) for k, v in want.items() if got[k] != v}


@pytest.mark.parametrize("name, layers", [("phi4-mini-3.8b", 32), ("phi4-mini-3.8b-tp4", 4)])
def test_phi4_mini_files_run_as_pinned(name, layers):
    """Every pinned value as loaded, and every field that the pin does not
    name at its declared default: a field that a later model adds, with a
    default that runs what runs today, leaves Phi-4-mini as it was."""
    cell = _file_cell(_conf(name))
    assert _off_pin(cell.model_config(), dict(PHI4_MODEL_CONFIG, num_layers=layers)) == {}
    dims = cell.dims()
    assert type(dims) is counts.Dims  # no "counts" key: the dense counts
    assert dataclasses.asdict(dims) == dict(PHI4_DIMS, layers=layers)


def _as(cls, cfg, **kw):
    """``cfg`` as an instance of ``cls``, a subclass with more fields."""
    return cls(**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}, **kw)


@dataclasses.dataclass(frozen=True)
class _WithScale(ModelConfig):
    router_scale: float = 1.0  # a field a later model adds, at what runs today


@pytest.mark.parametrize(
    "make, pin_edit, off",
    [
        (lambda c: _as(_WithScale, c), {}, set()),
        (lambda c: _as(_WithScale, c, router_scale=16.0), {}, {"router_scale"}),
        (lambda c: _as(_WithScale, c).replace(loss_chunk=0), {}, {"loss_chunk"}),
        (lambda c: _as(_WithScale, c), {"rope_factor": 1.0}, {"rope_factor"}),
    ],
    ids=["new-field-at-default", "new-field-moved", "pinned-value-moved", "pinned-key-gone"],
)
def test_the_pin_admits_a_new_defaulted_field(monkeypatch, make, pin_edit, off):
    real = harness.repo_config
    monkeypatch.setattr(harness, "repo_config", lambda name: make(real(name)))
    cfg = _file_cell(_conf("phi4-mini-3.8b")).model_config()
    assert set(_off_pin(cfg, {**PHI4_MODEL_CONFIG, **pin_edit})) == off


def test_a_deepseek_v2_shaped_file_places_every_key(tmp_path):
    """MLA and MoE keys at reduced widths, with no head_dim and no
    partial_rotary_factor, through ``load_cell``."""
    from repro.models import build_model

    cell = _load(tmp_path, DATA / "deepseek-v2-shaped.json")
    pub, cfg = cell.published, cell.model_config()
    assert "head_dim" not in pub and "partial_rotary_factor" not in pub
    repo = harness.repo_config("deepseek-v2-236b-reduced")
    assert cfg.attention == "mla"
    assert cfg.head_dim == repo.head_dim != pub["hidden_size"] // pub["num_attention_heads"]
    placed = {
        "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
        "v_head_dim": "v_head_dim", "num_experts": "n_routed_experts",
        "experts_per_token": "num_experts_per_tok", "num_shared_experts": "n_shared_experts",
        "moe_d_ff": "moe_intermediate_size", "first_dense_layers": "first_k_dense_replace",
        "num_layers": "num_hidden_layers", "d_model": "hidden_size", "vocab_size": "vocab_size",
        "max_seq_len": "max_position_embeddings",  # the file's own "fields" map
    }
    for f, k in placed.items():
        assert getattr(cfg, f) == pub[k] != getattr(repo, f), f
    assert cfg.capacity_factor == 2.0 != repo.capacity_factor  # "repo_fields", assumed
    build_model(cfg).abstract_params()  # the program takes the configuration as run


# fields in which a repo configuration could state DeepSeek-V2's router and
# RoPE, each defaulting to what the repo's model runs today
ROUTER_AND_ROPE = {
    "router_scoring": "softmax", "router_topk_method": "greedy", "router_groups": 1,
    "router_topk_groups": 1, "router_renormalise": True, "router_scale": 1.0,
    "router_seq_aux": False, "rope_scaling": None,
}
DSV2 = DATA / "deepseek-v2-published.json"


def _stand_in(monkeypatch, drop: str = ""):
    """Serve ``harness.repo_config`` a subclass of the repo's configuration
    with the fields of :data:`ROUTER_AND_ROPE`, but ``drop``."""
    cls = dataclasses.make_dataclass(
        "StandIn",
        [(f, Any, dataclasses.field(default=v)) for f, v in ROUTER_AND_ROPE.items() if f != drop],
        bases=(ModelConfig,),
        frozen=True,
    )
    real = harness.repo_config
    monkeypatch.setattr(harness, "repo_config", lambda name: _as(cls, real(name)))


def test_the_published_deepseek_v2_file_sets_its_router_and_rope(tmp_path, monkeypatch):
    """The catalog's config verbatim, at every published width: its router
    and YaRN keys arrive on the stand-in's fields as published."""
    _stand_in(monkeypatch)
    cell = _load(tmp_path, DSV2)
    pub, cfg = cell.published, cell.model_config()
    assert cell.config["reduced"] == []
    assert sorted(cell.config["fields"].values()) == sorted(ROUTER_AND_ROPE)
    fields = cell.config["fields"]
    assert {f: getattr(cfg, f) for f in fields.values()} == {f: pub[k] for k, f in fields.items()}
    assert (cfg.router_topk_method, cfg.router_groups, cfg.router_topk_groups) == (
        "group_limited_greedy", 8, 3
    )
    assert (cfg.router_renormalise, cfg.router_scale, cfg.router_seq_aux) == (False, 16, True)
    assert cfg.rope_scaling["type"] == "yarn"
    assert (cfg.rope_scaling["factor"], cfg.rope_scaling["mscale_all_dim"]) == (40, 0.707)
    widths = {
        "num_layers": 60, "d_model": 5120, "num_heads": 128, "num_kv_heads": 128, "d_ff": 12288,
        "vocab_size": 102400, "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts": 160, "experts_per_token": 6,
        "num_shared_experts": 2, "moe_d_ff": 1536, "first_dense_layers": 1,
    }
    assert {f: getattr(cfg, f) for f in widths} == widths


@pytest.mark.parametrize("drop", sorted(ROUTER_AND_ROPE))
def test_a_mapped_key_refuses_a_repo_config_without_its_field(tmp_path, monkeypatch, drop):
    _stand_in(monkeypatch, drop=drop)
    cell = _load(tmp_path, DSV2)
    key = next(k for k, f in cell.config["fields"].items() if f == drop)
    with pytest.raises(ValueError, match=f"{key}.*{drop}"):
        cell.model_config()


@pytest.mark.parametrize(
    "key, refused",
    [
        ("topk_method", True), ("n_group", True), ("topk_group", True),
        ("norm_topk_prob", True), ("routed_scaling_factor", True), ("seq_aux", True),
        ("rope_scaling", True),
        ("scoring_func", False),  # judged, and softmax is what the repo's router runs
    ],
)
def test_a_runs_key_is_judged_only_where_the_file_does_not_map_it(
    tmp_path, monkeypatch, key, refused
):
    _stand_in(monkeypatch)
    conf = json.loads(DSV2.read_text())
    del conf["fields"][key]
    (tmp_path / "dsv2.json").write_text(json.dumps(conf))
    cell = _load(tmp_path, tmp_path / "dsv2.json")
    if refused:
        with pytest.raises(ValueError, match=f"cannot run.*{key}"):
            cell.model_config()
    else:
        assert cell.model_config().router_scoring == "softmax"  # the field's default


@pytest.mark.parametrize(
    "edit, field, value",
    [
        # a published list, mapped in "fields"
        (lambda c: c.update(full_attention_layers=[0, 3],
                            fields={"full_attention_layers": "global_layers"}),
         "global_layers", (0, 3)),
        # a nested list in "repo_fields"
        (lambda c: c.update(repo_fields={"sharding_rules": [["mlp", "data"]]},
                            assumed=dict(c["assumed"], sharding_rules=[["mlp", "data"]]),
                            assumed_why=dict(c["assumed_why"], sharding_rules="why")),
         "sharding_rules", (("mlp", "data"),)),
    ],
    ids=["fields", "repo_fields"],
)
def test_a_list_sets_a_tuple(edit, field, value):
    conf = _conf("phi4-mini-3.8b")
    edit(conf)
    cfg = _file_cell(conf).model_config()
    assert getattr(cfg, field) == value and type(getattr(cfg, field)) is tuple
    hash(cfg)  # a frozen configuration with a list in it could not be hashed


PHI4 = "phi4-mini-3.8b"


@pytest.mark.parametrize(
    "file, edit, named",
    [
        # a published key nobody places
        (PHI4, lambda c: c.update(rope_type="yarn"), "rope_type"),
        # a harness key misspelt reads as a published key nobody places
        (PHI4, lambda c: c.update(reduce=c.pop("reduced")), "reduce"),
        # listed under "unmapped" without a reason
        (PHI4, lambda c: c["unmapped"].update(max_position_embeddings=""),
         "max_position_embeddings"),
        # repo_fields in neither "reduced" nor "assumed"
        (PHI4, lambda c: c.update(repo_fields={"capacity_factor": 2.0}), "capacity_factor"),
        # ... in "assumed" without its reason in "assumed_why"
        (PHI4, lambda c: c.update(repo_fields={"capacity_factor": 2.0},
                                  assumed=dict(c["assumed"], capacity_factor=2.0)),
         "capacity_factor"),
        # ... in "reduced" without its reason in "departures"
        (PHI4, lambda c: c.update(repo_fields={"capacity_factor": 2.0},
                                  reduced=c["reduced"] + ["capacity_factor"]), "capacity_factor"),
        # ... a field that a published key sets
        (PHI4, lambda c: c.update(repo_fields={"d_model": 64}, reduced=c["reduced"] + ["d_model"],
                                  departures=dict(c["departures"], d_model="why")), "d_model"),
        # published values the repo's model cannot run
        (PHI4, lambda c: c.update(hidden_act="gelu"), "hidden_act"),
        (PHI4, lambda c: c.update(partial_rotary_factor=0.75), "partial_rotary_factor"),
        (PHI4, lambda c: c.update(rope_scaling={"type": "longrope"}), "rope_scaling"),
        (PHI4, lambda c: c.update(attention_bias=True), "attention_bias"),
        (PHI4, lambda c: c.update(mlp_bias=True), "mlp_bias"),
        (PHI4, lambda c: c.update(lm_head_bias=True), "lm_head_bias"),
        (PHI4, lambda c: c.update(sliding_window=4096), "sliding_window"),
        # ... a router the repo's does not run
        ("dsv2", lambda c: c.update(routed_scaling_factor=16), "routed_scaling_factor"),
        ("dsv2", lambda c: c.update(topk_method="group_limited_greedy"), "topk_method"),
        ("dsv2", lambda c: c.update(n_group=8, topk_group=3), "n_group"),
        ("dsv2", lambda c: c.update(norm_topk_prob=False), "norm_topk_prob"),
        ("dsv2", lambda c: c.update(scoring_func="sigmoid"), "scoring_func"),
        ("dsv2", lambda c: c.update(moe_layer_freq=2), "moe_layer_freq"),
        ("dsv2", lambda c: c.update(seq_aux=True), "seq_aux"),
    ],
)
def test_model_config_refuses(file, edit, named):
    conf = _dsv2() if file == "dsv2" else _conf(file)
    edit(conf)
    with pytest.raises(ValueError, match=named):
        _file_cell(conf).model_config()


def test_a_file_names_its_counts_module(tmp_path, monkeypatch):
    """The counts module gets the published keys and the file's
    ``repo_fields``: the experts held, where a chip holds a share."""
    (tmp_path / "counts_probe.py").write_text(
        "class Dims:\n"
        "    @classmethod\n"
        "    def from_published(cls, hf, repo_fields):\n"
        "        d = cls()\n"
        "        d.experts, d.repo_fields = hf['n_routed_experts'], repo_fields\n"
        "        return d\n"
    )
    monkeypatch.syspath_prepend(str(tmp_path))
    conf = _dsv2()
    try:
        dims = _file_cell(dict(conf, counts="counts_probe")).dims()
        assert type(dims).__module__ == "counts_probe" and dims.experts == 6
        assert dims.repo_fields == conf["repo_fields"] == {"capacity_factor": 2.0}
    finally:
        sys.modules.pop("counts_probe", None)
    with pytest.raises(ValueError, match="counts"):
        _file_cell(dict(conf, counts="json")).dims()


def test_the_dense_counts_ignore_repo_fields():
    pub = _file_cell(_conf("phi4-mini-3.8b")).published
    assert counts.Dims.from_published(pub, {"kv_pad_to": 16}) == _phi4()
