"""The comparison that decides ``correct``.

Once the window has closed (and the requests in flight have run on
for a bounded time, admitting nothing), a sample drawn from the seed of
the finished requests (the longest always among them) is run through the
plain reference (``bench/reference``), once per request over its prompt
and the tokens it was served.  At every served position the reference's
best logit minus its logit for the served token is the gap; the widest
gap over the sample is compared with the cell's limit
(``bench/limits/<cell>.json``).  The served tokens include the first,
sampled from the prefill (batched prefill, or the tail streamed over
shared pages after a prompt-cache hit), and every later one from the
paged decode step, all greedy.

The control reads, at the same positions of the same sequences, the gap
of the token that the reference computed at the precision below the
configuration's puts first.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference import model as ref

#: what the sample aims at: some hundreds of served tokens
TARGET_TOKENS = 384
MAX_REQUESTS = 6


def sample(done, seed: int) -> list:
    """Of the finished requests ``done``, those to compare: the longest,
    then others in an order drawn from ``seed``, until the sample holds
    ``TARGET_TOKENS`` served tokens or ``MAX_REQUESTS`` requests."""
    if not done:
        return []
    longest = max(done, key=lambda t: (len(t.req.generated), -t.uid))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    pick, n = [longest], len(longest.req.generated) + 1
    for t in rest:
        if n >= TARGET_TOKENS or len(pick) >= MAX_REQUESTS:
            break
        pick.append(t)
        n += len(t.req.generated) + 1
    return pick


def served(t, first_token: int):
    """(prompt, served tokens) of one finished request."""
    return (np.asarray(t.req.prompt, np.int32),
            np.asarray([first_token] + list(t.req.generated), np.int32))


def _inputs(prompt, toks, seq_len: int, n_pos: int):
    plen, n = len(prompt), len(toks)
    if plen + n - 1 > seq_len or n > n_pos:
        raise ValueError(f"sequence of {plen}+{n} tokens does not fit the "
                         f"reference's {seq_len} positions / {n_pos} scores")
    seq = np.zeros(seq_len, np.int32)
    seq[:plen] = prompt
    seq[plen:plen + n - 1] = toks[:-1]
    pos = np.zeros(n_pos, np.int32)
    pos[:n] = plen - 1 + np.arange(n)
    tok = np.zeros(n_pos, np.int32)
    tok[:n] = toks
    valid = np.arange(n_pos) < n
    return seq, pos, tok, valid


@jax.jit
def _gap(ref_logits, chosen, valid):
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return jnp.max(jnp.where(valid, best - got, -jnp.inf))


def widest_gaps(params, arch, seqs, seq_len: int, n_pos: int,
                control: Optional[str] = None) -> List[float]:
    """Per sequence, the widest gap between the reference's best logit
    and its logit for the token served (``control`` None) or for the
    token the reference at ``control`` precision puts first."""
    spec = ref.spec_of(arch)
    out = []
    for prompt, toks in seqs:
        seq, pos, tok, valid = _inputs(prompt, toks, seq_len, n_pos)
        seq, pos = jnp.asarray(seq), jnp.asarray(pos)
        logits = ref.logits_at(params, seq, pos, spec=spec)
        if control is None:
            chosen = jnp.asarray(tok)
        else:
            low = ref.logits_at(params, seq, pos, spec=spec,
                                operand=control)
            chosen = jnp.argmax(low, axis=-1).astype(jnp.int32)
        out.append(float(_gap(logits, chosen, jnp.asarray(valid))))
    return out
