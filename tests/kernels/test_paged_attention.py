"""Paged decode attention kernel (interpret mode on CPU) against its jnp
oracle and against dense attention over each lane's flat cache.

A lane's pages are scattered through the pool out of order, its table row
has holes (unmapped entries on the zero page), and an idle lane has length
0 and a row on the scratch page; the lengths cover one token, both sides of
a page boundary and a full row.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.paged_attention import fetch_pages
from repro.kernels.ref import attention_ref, paged_attention_ref

PAGE, PAGES = 64, 4  # a row of 256 tokens
ZERO, SCRATCH = 0, 1
# tokens already in each lane's pages (its write index); 0 is an idle lane
LENGTHS = [0, 1, 63, 64, 128, PAGE * PAGES - 1]


def _lanes(rng, lengths, num_phys):
    """Page table rows: each lane's pages drawn out of order from the pool,
    every entry past its length left unmapped (zero page)."""
    free = list(rng.permutation(np.arange(2, num_phys)))
    table = np.full((len(lengths), PAGES), ZERO, np.int32)
    for n, length in enumerate(lengths):
        if length == 0:
            table[n, :] = SCRATCH
            continue
        for j in range(-(-(length + 1) // PAGE)):
            table[n, j] = free.pop()
    return table


def _flat(pages, table, lane, layer):
    """Lane ``lane``'s logical cache, (KV, PAGES * PAGE, Dh)."""
    c = pages[layer][table[lane]]  # (P, KV, page, Dh)
    return c.transpose(1, 0, 2, 3).reshape(c.shape[1], -1, c.shape[-1])


@pytest.mark.parametrize(
    "heads,kv_heads,head_dim",
    # Phi-4-mini's padded 48/16; an unpadded 4:1; heads past one VMEM block
    [(48, 16, 128), (8, 2, 64), (64, 32, 256)],
)
def test_paged_kernel_matches_oracle_and_dense(heads, kv_heads, head_dim):
    rng = np.random.default_rng(heads)
    layers, N = 2, len(LENGTHS)
    num_phys = 2 + N * PAGES + 3  # a few pages no lane holds
    ks = jax.random.split(jax.random.PRNGKey(heads), 5)
    bf16 = jnp.bfloat16
    shape = (layers, num_phys, kv_heads, PAGE, head_dim)
    k_pages = jax.random.normal(ks[0], shape).astype(bf16).at[:, ZERO].set(0)
    v_pages = jax.random.normal(ks[1], shape).astype(bf16).at[:, ZERO].set(0)
    q = jax.random.normal(ks[2], (N, heads, head_dim)).astype(bf16)
    k_new = jax.random.normal(ks[3], (N, kv_heads, head_dim)).astype(bf16)
    v_new = jax.random.normal(ks[4], (N, kv_heads, head_dim)).astype(bf16)
    table = jnp.asarray(_lanes(rng, LENGTHS, num_phys))
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    layer = jnp.asarray(1, jnp.int32)

    args = (q, k_new, v_new, k_pages, v_pages, table, lengths, layer)
    got = np.asarray(ops.paged_attention(*args, interpret=True), np.float32)
    oracle = np.asarray(paged_attention_ref(*args), np.float32)
    np.testing.assert_allclose(got, oracle, atol=2e-2, rtol=2e-2)

    for n, length in enumerate(LENGTHS):  # dense attention over the flat cache
        k = _flat(k_pages, np.asarray(table), n, 1).at[:, length].set(k_new[n])
        v = _flat(v_pages, np.asarray(table), n, 1).at[:, length].set(v_new[n])
        want = attention_ref(
            q[n][None, :, None], k[None], v[None], causal=False, k_len=length + 1
        )[0, :, 0]
        np.testing.assert_allclose(got[n], np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)

    # the pages past a lane's length, and an idle lane's row, are never read
    poisoned = (k_pages.at[:, SCRATCH].set(jnp.nan), v_pages.at[:, SCRATCH].set(jnp.nan))
    for n, length in enumerate(LENGTHS):
        read = -(-length // PAGE)  # pages the kernel reads for this lane
        for page in np.asarray(table[n])[read:]:
            if page not in (ZERO, SCRATCH):
                poisoned = tuple(p.at[:, page].set(jnp.nan) for p in poisoned)
    again = ops.paged_attention(q, k_new, v_new, *poisoned, table, lengths, layer, interpret=True)
    np.testing.assert_array_equal(np.asarray(again, np.float32), got)


def test_steps_past_a_length_fetch_no_new_page():
    """Each grid step inside a lane's length reads its table entry; every
    other step repeats the page before it, so the pipeline copies nothing."""
    table = jnp.asarray([[7, 3, 9], [1, 1, 1], [5, 2, 0], [1, 1, 1]], jnp.int32)
    lengths = jnp.asarray([70, 0, 10, 0], jnp.int32)
    fetch = np.asarray(fetch_pages(table, lengths, PAGE))
    np.testing.assert_array_equal(fetch, [7, 3, 3, 3, 3, 3, 5, 5, 5, 5, 5, 5])
    lead_idle = np.asarray(fetch_pages(table[::-1], lengths[::-1], PAGE))
    np.testing.assert_array_equal(lead_idle[:4], [5, 5, 5, 5])  # the first page read
