"""What the reference computes for the comparisons that decide `correct`.

Serving: teacher-forced logits over each sampled prompt with the tokens
the engine served, and for each served token the gap by which the
reference scores it below its own best token.  The control path (fp8)
reads, at the same positions, the gap of the token that fp8 puts first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import decoder

ROW_BLOCK = 256          # served positions whose logits are read at once


def served_gaps(conf: dict, w: dict, seqs, *, pad_to: int,
                control: bool = False):
    """seqs: [(prompt, served)] int arrays.  Returns (gaps, control_gaps):
    per served token, reference best logit minus the reference logit of
    the served token; and, with `control`, of the fp8 path's first token
    at the same position (None otherwise)."""
    with jax.default_matmul_precision("highest"):
        hid = jax.jit(lambda w, t: decoder.hidden(conf, w, t)[0])
        hid8 = jax.jit(lambda w, t: decoder.hidden(conf, w, t, fp8=True)[0])

        @jax.jit
        def block(w, h, h8, rows, toks):
            hr = h[rows]
            lg = decoder.logits(conf, w, hr)
            best = jnp.max(lg, -1)
            gap = best - jnp.take_along_axis(lg, toks[:, None], -1)[:, 0]
            if h8 is None:
                return gap, gap
            first8 = jnp.argmax(decoder.logits(conf, w, h8[rows], fp8=True),
                                -1)
            return gap, best - jnp.take_along_axis(lg, first8[:, None],
                                                   -1)[:, 0]

        gaps, cgaps = [], []
        for prompt, served in seqs:
            seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            if len(seq) > pad_to:
                raise ValueError(f"sequence of {len(seq)} > {pad_to}")
            toks = np.zeros((1, pad_to), np.int32)
            toks[0, :len(seq)] = seq
            h = hid(w, toks)
            h8 = hid8(w, toks) if control else None
            first = len(prompt) - 1            # predicts served[0]
            for s in range(0, len(served), ROW_BLOCK):
                n = min(ROW_BLOCK, len(served) - s)
                rows = np.full(ROW_BLOCK, first + s, np.int32)
                rows[:n] = first + s + np.arange(n)
                tk = np.zeros(ROW_BLOCK, np.int32)
                tk[:n] = served[s:s + n]
                g, cg = block(w, h, h8, rows, tk)
                gaps.append(np.asarray(g)[:n])
                cgaps.append(np.asarray(cg)[:n])
    return (np.concatenate(gaps),
            np.concatenate(cgaps) if control else None)

