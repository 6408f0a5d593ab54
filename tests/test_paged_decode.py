"""The paged-decode Pallas kernel (interpret mode) against its gather
reference, `layers.kv_attend_gather`: gather every page of the slot, add
the new row, attend with `_attend_mq` under the causal(+window) mask."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels import paged_decode as pd
from repro.models import layers as L

HD, K, LAYERS, LAYER = 64, 2, 2, 1


def _case(group, page_size, max_seq, seed=0):
    """A two-layer stacked pool of random K/V, one slot per interesting
    position (0, page_size-1, page_size, mid-page, max_seq-1) on its own
    shuffled pages, and two inactive slots (position 0, null table)."""
    rng = np.random.default_rng(seed)
    pos = [0, page_size - 1, page_size, page_size + page_size // 2,
           max_seq - 1]
    active = len(pos)
    B = active + 2
    max_pages = max_seq // page_size
    n_pages = 1 + active * max_pages
    table = np.zeros((B, max_pages), np.int32)            # 0: null page
    table[:active] = 1 + rng.permutation(n_pages - 1).reshape(active, -1)
    pool = {c: jnp.asarray(rng.normal(size=(LAYERS, n_pages, page_size,
                                            K * HD)), jnp.bfloat16)
            for c in "kv"}
    q = jnp.asarray(rng.normal(size=(B, K * group, HD)), jnp.bfloat16)
    new = {c: jnp.asarray(rng.normal(size=(B, K, HD)), jnp.bfloat16)
           for c in "kv"}
    return (q, new, pool, jnp.asarray(table),
            jnp.asarray(pos + [0] * (B - active), jnp.int32), active)


def _kernel(q, new, pool, table, pos, page_size, window, softcap,
            pages_per_block):
    qf = q.astype(jnp.float32) / math.sqrt(HD)
    return pd.paged_decode_attention(
        qf, new["k"], new["v"], pool["k"], pool["v"], jnp.int32(LAYER),
        table, pos, page_size=page_size, window=window, softcap=softcap,
        pages_per_block=pages_per_block, interpret=True)


def _gather_path(q, new, pool, table, pos, page_size, window, softcap):
    cfg = dataclasses.replace(smoke_config("qwen2-0.5b"), head_dim=HD,
                              softcap=softcap)
    qf = q.astype(jnp.float32) / math.sqrt(HD)
    return L.kv_attend_gather(cfg, qf, new["k"], new["v"], pool,
                              jnp.int32(LAYER), table, pos, window=window)


@pytest.mark.parametrize("window,softcap", [(None, None), (20, 5.0)],
                         ids=["causal", "window+softcap"])
@pytest.mark.parametrize("page_size,max_seq,pages_per_block",
                         [(16, 64, 2), (8, 48, 4)], ids=["ps16", "ps8"])
@pytest.mark.parametrize("group", [7, 1])
def test_paged_decode_matches_gather_path(group, page_size, max_seq,
                                          pages_per_block, window, softcap):
    q, new, pool, table, pos, active = _case(group, page_size, max_seq)
    got = np.asarray(_kernel(q, new, pool, table, pos, page_size, window,
                             softcap, pages_per_block))
    want = np.asarray(_gather_path(q, new, pool, table, pos, page_size,
                                   window, softcap))
    # live slots: the same arithmetic up to f32 accumulation order
    np.testing.assert_allclose(got[:active], want[:active],
                               rtol=1e-5, atol=1e-6)
    # an inactive slot reads no page: each head attends its own new row
    v_own = np.repeat(np.asarray(new["v"], np.float32), group, axis=1)
    np.testing.assert_array_equal(got[active:], v_own[active:])


def test_paged_decode_row_is_batch_independent():
    """A slot's output is bitwise the same whether every other slot is
    live or inactive (position 0 on the null table row)."""
    q, new, pool, table, pos, active = _case(7, 16, 64, seed=3)
    batched = np.asarray(_kernel(q, new, pool, table, pos, 16, None, None,
                                 2))
    for b in range(active):
        alone_pos = jnp.zeros_like(pos).at[b].set(pos[b])
        alone_table = jnp.zeros_like(table).at[b].set(table[b])
        alone = np.asarray(_kernel(q, new, pool, alone_table, alone_pos,
                                   16, None, None, 2))
        np.testing.assert_array_equal(batched[b], alone[b])


def test_page_span_reads_only_live_pages():
    pos = np.array([0, 1, 16, 17, 100, 2047])
    first, count = pd.page_span(pos, 16)
    assert first.tolist() == [0] * 6
    assert count.tolist() == [0, 1, 1, 2, 7, 128]
    first, count = pd.page_span(pos, 16, window=20)
    # keys (pos - 20, pos): 100 -> 81..99 on pages 5, 6
    assert first.tolist() == [0, 0, 0, 0, 5, 126]
    assert count.tolist() == [0, 1, 1, 2, 2, 2]
    jfirst, jcount = pd.page_span(jnp.asarray(pos), 16, window=20)
    assert jfirst.tolist() == first.tolist()
    assert jcount.tolist() == count.tolist()
