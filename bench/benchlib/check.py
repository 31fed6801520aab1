"""Decide ``correct``: served tokens against the float32 reference.

Once the window has closed and the program is gone, a sample of finished
requests drawn from the seed, the longest among them, is run through the
reference once each, prompt and served tokens together.  The number
compared is the widest gap by which a served token's reference logit lies
below the reference's best logit at that position.  Tokens are greedy, so
a sound program serves the reference's best token or one within rounding
of it.

The control (``control=True``) puts the reference computed with float8
matrix products in the program's place: at the same positions it reads
the gap of the token the float8 logits put first.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchlib import stats, traffic


def _gap(z, tokens) -> np.ndarray:
    """How far below each row's best logit the chosen token's logit lies."""
    tokens = jnp.asarray(tokens)
    picked = jnp.take_along_axis(z, tokens[:, None], axis=1)[:, 0]
    return np.asarray(z.max(axis=1) - picked)


def sample(records: list, seed: int, min_tokens: int,
           max_requests: int) -> list:
    """Finished requests from the window: the longest, then others in a
    seeded order until ``min_tokens`` served tokens are covered."""
    done = [r for r in stats.done(records) if r["n_out"] > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_len"] + r["n_out"])
    rest = [r for r in done if r is not longest]
    order = traffic.rng_for(seed, 11).permutation(len(rest))
    out, n = [longest], longest["n_out"]
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += rest[i]["n_out"]
    return out


def gaps(ref, c: dict, seed: int, requests: list, control: bool = False,
         pad_to: int = 512) -> dict:
    """Reference gaps of the served tokens (and, with ``control``, of the
    float8 control's first choices) over the sampled requests."""
    by_fn: dict = {}
    for r in requests:
        by_fn.setdefault(r["fn"], []).append(r)
    served, ctrl, n_tok = [], [], 0
    for fn, rs in sorted(by_fn.items()):
        seqs = [np.concatenate([r["prompt"], r["tokens"][:-1]]) for r in rs]
        pos = [r["prompt_len"] - 1 + np.arange(r["n_out"]) for r in rs]
        ref_logits = ref.logits_at(c, seed, fn, seqs, pos, pad_to=pad_to)
        for r, z in zip(rs, ref_logits):
            served.append(_gap(z, r["tokens"]))
            n_tok += len(r["tokens"])
        if control:
            low = ref.logits_at(c, seed, fn, seqs, pos, mode="fp8",
                                pad_to=pad_to)
            for z, zl in zip(ref_logits, low):
                ctrl.append(_gap(z, jnp.argmax(zl, axis=1)))
    out = {"max_logit_gap": float(np.max(np.concatenate(served))),
           "tokens": n_tok, "requests": len(requests)}
    if control:
        out["control_max_logit_gap"] = float(np.max(np.concatenate(ctrl)))
    return out
