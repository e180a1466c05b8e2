"""The sequential reference the engine's tokens are compared with: each
request decoded alone, greedily, one token at a time, through the model's
own ``prefill`` and ``decode_step``."""
import jax
import jax.numpy as jnp

from repro.serve.kv import pad_caches_to


def sequential_decode(model, params, prompt, budget, width):
    """``budget`` greedy tokens after ``prompt``, at ``width`` KV capacity
    (the engine's max_len): the prefill cache is zero-padded to ``width``
    and sliding-window rings grow to ``min(window, width)``, as the
    engine's cache holds them, so both programs mask identically."""
    window = model.cfg.window
    logits, caches = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(prompt[None, :])})
    caches = pad_caches_to(
        caches,
        width - int(prompt.size),
        ring_w=None if window is None else min(window, width),
    )
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out = [int(tok[0, 0])]
    decode = jax.jit(model.decode_step)
    for i in range(budget - 1):
        logits, caches = decode(params, tok, caches, jnp.asarray(prompt.size + i, jnp.int32))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out.append(int(tok[0, 0]))
    return out
