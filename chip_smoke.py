"""Chip smoke test: the system's main paths on a TPU, at published widths.

One chip (the default) serves TinyLlama-1.1B (22 layers, d_model 2048, 32
heads, 4 KV heads, d_ff 5632, vocab 32000, bf16, random weights from
``--seed``) through ``ServeEngine`` with the paged KV layout: 8 requests,
prompts of 128-512 tokens, 32-64 new tokens each, one of them streamed.
Every request's tokens are compared with sequential greedy decode at the
same cache width.

``--chips 4`` runs only the sharded training path on a (data 1 x model 4)
mesh instead: one AdamW step of a 4-layer cut of TinyLlama on device 0
alone against the same step on the mesh, then a few ``Trainer`` steps of
the full 22-layer model, whose training state does not fit one chip.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4

It exits non-zero, and prints no result, unless JAX's first device is a
TPU. Its last line of output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime import Trainer, TrainerConfig  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402
from repro.serve.kv import pad_caches_to  # noqa: E402

ARCH = "tinyllama-1.1b"

# A divergence from sequential decode is accepted only at a near-tie: both
# diverging tokens' float32 logits lie within this much of the float32 top
# logit at that position. bf16 keeps 8 mantissa bits, so two programs that
# pad or tile the same sum differently disagree by about 2^-8 of a logit's
# magnitude; with logits of order 1 that is below 0.02.
NEAR_TIE = 0.05

# One step on one chip vs the same step on four: loss and grad-norm agree to
# this relative tolerance (five bf16 ulps, 5 * 2^-8).
STEP_RTOL = 2e-2

# The served traffic and the engine that takes it.
REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, (128, 512), (32, 64)
SLOTS, MAX_LEN, BUCKETS = 4, 1024, (256, 512)
TRAIN_STEPS = 3


# -- serving on one chip ---------------------------------------------------------


def make_requests(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, vocab, size=int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1)))
        .astype(np.int32)
        for _ in range(REQUESTS)
    ]
    budgets = [int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1)) for _ in range(REQUESTS)]
    return prompts, budgets


def sequential_reference(model, params, prompts, budgets, width: int):
    """Each request decoded alone, greedily, one token at a time, with its
    prefill cache zero-padded to ``width`` (the engine's max_len), so both
    programs attend over caches of one width: in bf16 the reduction tiling
    over differently padded widths can flip argmax at a near-tie."""
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=(2,))
    outs = []
    for prompt, budget in zip(prompts, budgets):
        logits, caches = prefill(params, {"tokens": jnp.asarray(prompt[None, :])})
        caches = pad_caches_to(caches, width - int(prompt.size))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out = [int(tok[0, 0])]
        for i in range(budget - 1):
            logits, caches = decode(params, tok, caches, jnp.asarray(prompt.size + i, jnp.int32))
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            out.append(int(tok[0, 0]))
        outs.append(out)
    return outs


def near_tie(prefill32, params32, prompt, prefix, toks, width: int) -> dict:
    """float32 logits after ``prompt + prefix``: the top-2 gap, and how far
    each of ``toks`` lies below the top logit."""
    seq = np.concatenate([prompt, np.asarray(prefix, np.int32)])
    padded = np.zeros((1, width), np.int32)
    padded[0, : seq.size] = seq  # right padding is causally invisible
    logits, _ = prefill32(
        params32, {"tokens": jnp.asarray(padded)}, last_pos=jnp.asarray(seq.size - 1)
    )
    lf = np.asarray(logits[0, -1], np.float64)
    top2 = np.sort(lf)[-2:]
    return {
        "top2_gap": float(top2[1] - top2[0]),
        "below_top": [float(top2[1] - lf[t]) for t in toks],
    }


def serve_phase(cfg, *, seed: int = 0) -> dict:
    """Serve seeded requests through ``ServeEngine``; compare every request
    with sequential greedy decode. Raises on any failed request."""
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    prompts, budgets = make_requests(cfg.vocab_size, seed)

    with ServeEngine(
        model, params, max_slots=SLOTS, max_len=MAX_LEN, prefill_buckets=BUCKETS
    ) as engine:
        t0 = time.perf_counter()
        # one prompt per bucket compiles both prefills and the decode tick
        engine.generate([np.zeros(b, np.int32) for b in BUCKETS], 2)
        compile_s = time.perf_counter() - t0
        warm = engine.stats()
        t0 = time.perf_counter()
        handles = [engine.submit(p, n) for p, n in zip(prompts, budgets)]
        streamed = [int(t) for t in handles[0].iter_tokens(timeout=600)]
        outs = [list(map(int, h.result(600))) for h in handles]
        serve_s = time.perf_counter() - t0
        stats = engine.stats()

    if streamed != outs[0]:
        raise AssertionError("streamed tokens differ from the request's result")
    for i, (o, n) in enumerate(zip(outs, budgets)):
        if len(o) != n:
            raise AssertionError(f"request {i}: {len(o)} tokens, asked for {n}")
    if stats["deadline_misses"] or stats["truncations"]:
        raise AssertionError(f"engine dropped work: {stats}")

    refs = sequential_reference(model, params, prompts, budgets, width=MAX_LEN)
    # (request, position of its first token that differs from the reference)
    diverged = [
        (i, next(j for j, (a, b) in enumerate(zip(o, r)) if a != b))
        for i, (o, r) in enumerate(zip(outs, refs))
        if o != r
    ]
    divergences = []
    if diverged:
        prefill32 = jax.jit(build_model(cfg.replace(dtype="float32")).prefill)
        params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        for i, j in diverged:
            tie = near_tie(
                prefill32, params32, prompts[i], refs[i][:j], (outs[i][j], refs[i][j]), MAX_LEN
            )
            divergences.append({"request": i, "position": j, **tie})
        del params32

    return {
        "compile_s": compile_s,
        "serve_s": serve_s,
        "tokens_matched": sum(budgets) - sum(len(outs[i]) - j for i, j in diverged),
        "tokens_total": sum(budgets),
        "divergences": divergences,
        "near_ties_ok": all(max(d["below_top"]) <= NEAR_TIE for d in divergences),
        "ticks": stats["ticks"] - warm["ticks"],
        "preemptions": stats["preemptions"],
        "peak_pages": stats["kv"]["peak_pages_live"],
        "pages_total": stats["kv"]["pages_total"],
    }


# -- sharded training on four chips ------------------------------------------------


def train_run(cfg, mesh, tcfg: TrainerConfig) -> list:
    """``Trainer`` steps with no checkpoint; returns the metric rows."""
    with tempfile.TemporaryDirectory() as ckpt, Trainer(cfg, tcfg, ckpt, mesh=mesh) as tr:
        out = tr.run(resume=False)
    return out["metrics"]


def train_phase(cfg, *, cut_layers: int = 4, seq_len: int = 256, batch: int = 8,
                seed: int = 0) -> dict:
    """The full model for ``TRAIN_STEPS`` steps on a (data 1 x model 4) mesh,
    then one step of a ``cut_layers`` cut on device 0 alone vs on the mesh."""
    mesh = make_host_mesh(model=4)

    def tcfg(n):
        return TrainerConfig(num_steps=n, checkpoint_every=0, log_every=1,
                             seq_len=seq_len, global_batch=batch, seed=seed)

    full = train_run(cfg, mesh, tcfg(TRAIN_STEPS))
    peaks = [peak_bytes(d) for d in mesh.devices.flat]
    cut = cfg.replace(num_layers=cut_layers)
    one = train_run(cut, None, tcfg(1))[0]
    sharded = train_run(cut, mesh, tcfg(1))[0]
    rel = {k: abs(sharded[k] - one[k]) / abs(one[k]) for k in ("loss", "grad_norm")}
    return {
        "full_losses": [r["loss"] for r in full],
        "peak_bytes_per_device": peaks,
        "one_device": {k: one[k] for k in ("loss", "grad_norm")},
        "sharded": {k: sharded[k] for k in ("loss", "grad_norm")},
        "rel_diff": rel,
        "ok": all(math.isfinite(x) for x in [r["loss"] for r in full])
        and all(v <= STEP_RTOL for v in rel.values()),
    }


# -- entry point ---------------------------------------------------------------------


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero when it is not a TPU."""
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu" or dev["count"] < chips:
        sys.exit(f"chip_smoke: needs {chips} TPU chip(s), JAX found {dev}")
    return dev


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu(args.chips)
    print(f"platform={dev['platform']} device_kind={dev['kind']} count={dev['count']}", flush=True)
    enable_compile_cache()
    cfg = get_config(ARCH)

    if args.chips == 4:
        r = train_phase(cfg, seed=args.seed)
        print(f"full {cfg.num_layers}-layer sharded steps: losses {r['full_losses']}")
        print(f"peak_bytes_in_use per device: {r['peak_bytes_per_device']}")
        print(f"one step, 4 layers: device 0 {r['one_device']} vs mesh {r['sharded']} "
              f"rel diff {r['rel_diff']} (tolerance {STEP_RTOL})")
        if not r["ok"]:
            sys.exit("chip_smoke: sharded training disagrees with one device or is not finite")
    else:
        r = serve_phase(cfg, seed=args.seed)
        peak = peak_bytes(jax.devices()[0])
        print(f"compile_s={r['compile_s']} serve_s={r['serve_s']} (bring-up facts, not metrics)")
        print(f"tokens matched {r['tokens_matched']}/{r['tokens_total']}; "
              f"divergences {r['divergences']} (near-tie bound {NEAR_TIE})")
        print(f"ticks={r['ticks']} preemptions={r['preemptions']} "
              f"peak_pages={r['peak_pages']}/{r['pages_total']} peak_bytes_in_use={peak}")
        if not r["near_ties_ok"]:
            sys.exit("chip_smoke: served tokens diverge from sequential decode beyond a near-tie")
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
