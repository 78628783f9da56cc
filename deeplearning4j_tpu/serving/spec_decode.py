"""Speculative decoding for the serving engine: draft -> verify.

The decode loop is memory-bound — every emitted token pays one full
read of the (already int8-quantized) weights plus the KV pages. The
paged-attention kernel and the fp8 KV cache cut the KV half of that
traffic; the weight half only amortizes by emitting MORE TOKENS PER
WEIGHT READ. That is what draft–verify buys:

- a cheap **draft** proposes ``k`` candidate tokens per slot
  (host-side n-gram self-draft by default — zero extra device
  programs — or any object with a ``propose`` method, e.g. a
  distilled checkpoint);
- ONE fixed-shape **verify** dispatch scores all ``k+1`` positions
  through the target model's own ``paged_rows`` (docs/SERVING.md,
  "What a served model brings"; a suffix prefill behind cached pages
  is the same method with one lane): the paged-attention op already
  handles multi-position suffix queries against a slot's page table;
- :func:`accept_tokens` keeps the longest draft prefix the target
  agrees with and emits one correction/bonus token on top, so every
  verify yields between 1 and ``k+1`` tokens for a single weight read.

Acceptance semantics (the standard rejection-sampling scheme,
specialized to a DETERMINISTIC draft ``q = delta(d_i)``):

- greedy (temperature 0): draft ``d_i`` is accepted iff it equals the
  target argmax at its position — the emitted sequence is EXACTLY the
  plain greedy rollout, token for token, which is why the engine's f32
  greedy token-identity gates carry over verbatim;
- temperature > 0: ``d_i`` is accepted with probability
  ``min(1, p(d_i)/q(d_i)) = p(d_i)``; on the first rejection the
  correction is sampled from the residual ``p`` with ``d_i`` removed
  and renormalized, and when every draft survives a bonus token is
  sampled from the target's next-position distribution. The emitted
  marginal is the target distribution exactly (Leviathan et al.,
  arXiv:2211.17192) — speculation changes latency, never the law.

Rejected drafts need no device-side cleanup: the verify program wrote
their K/V at positions ``>= pos + n_accepted``, the engine's position
rollback (``new_pos = pos + n_accepted``) makes those entries
invisible (attention admits flat position ``<= query pos`` only), and
the next verify overwrites them in place — see
``kv_pages.append_spec`` for the fp8-scale composition.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


class NGramDraft:
    """Prompt-lookup / n-gram self-draft (host-side, zero device
    programs): propose the ``k`` tokens that FOLLOWED the most recent
    earlier occurrence of the history's trailing n-gram, longest match
    first. Greedy rollouts of small models fall into repeating
    attractors quickly, and real text re-uses spans from its own
    prompt (quoting, code, JSON keys), so this trivial draft reaches
    high acceptance exactly where speculation pays — and it is fully
    deterministic, which the per-seed determinism tests pin."""

    def __init__(self, match_len: int = 3):
        if match_len < 1:
            raise ValueError("match_len must be >= 1")
        self.match_len = int(match_len)

    def propose(self, history: np.ndarray, k: int) -> np.ndarray:
        """``k`` candidate continuations of ``history`` (1-D int
        array of prompt + all emitted tokens). Always returns exactly
        ``k`` tokens; the fallback repeats the last token."""
        h = np.asarray(history, np.int32).ravel()
        L = h.size
        out: Optional[np.ndarray] = None
        for n in range(min(self.match_len, L - 1), 0, -1):
            pat = h[L - n:]
            # candidate starts strictly before the trailing occurrence
            win = np.lib.stride_tricks.sliding_window_view(h, n)[:L - n]
            hits = np.flatnonzero((win == pat).all(axis=1))
            if hits.size:
                j = int(hits[-1]) + n
                out = h[j:j + k]
                break
        if out is None or out.size == 0:
            last = h[-1] if L else np.int32(0)
            return np.full((k,), last, np.int32)
        if out.size < k:
            out = np.concatenate(
                [out, np.full((k - out.size,), out[-1], np.int32)])
        return out.astype(np.int32)


@dataclass
class SpecConfig:
    """Engine-level speculative-decoding configuration.

    k : drafts proposed (and verified) per slot per dispatch — the
        verify program's fixed width is ``k + 1`` and is AOT-warmed
        once per engine under the ``("verify", k)`` key.
    draft : ``"ngram"`` (the built-in self-draft) or any object with
        ``propose(history, k) -> np.ndarray`` — e.g. a wrapper over a
        truncated/distilled variant of the served checkpoint.
    match_len : longest trailing n-gram the n-gram draft matches on
        (ignored for custom draft objects).
    """

    k: int = 4
    draft: Any = "ngram"
    match_len: int = 3

    def __post_init__(self):
        self.k = int(self.k)
        if self.k < 1:
            raise ValueError(f"spec_decode k must be >= 1, got {self.k}")

    @classmethod
    def resolve(cls, spec) -> Optional["SpecConfig"]:
        """Canonicalize the engine's ``spec_decode=`` argument:
        None/False -> off, int -> k drafts of n-gram self-draft,
        "ngram" -> defaults, dict -> kwargs, SpecConfig -> itself."""
        if spec is None or spec is False:
            return None
        if isinstance(spec, cls):
            return spec
        if spec is True:
            return cls()
        if isinstance(spec, int):
            return cls(k=spec)
        if isinstance(spec, str):
            if spec != "ngram":
                raise ValueError(
                    f"unknown spec_decode draft {spec!r} (expected "
                    "'ngram', an int k, a dict, or a SpecConfig)")
            return cls()
        if isinstance(spec, dict):
            return cls(**spec)
        raise ValueError(f"cannot resolve spec_decode={spec!r}")

    def make_draft(self):
        if self.draft == "ngram":
            return NGramDraft(self.match_len)
        if not hasattr(self.draft, "propose"):
            raise ValueError(
                "spec_decode draft must be 'ngram' or expose "
                "propose(history, k)")
        return self.draft


def accept_tokens(logits, drafts, n_draft, keydata, temps):
    """Fixed-shape rejection-sampling acceptance over one verify
    dispatch's logits.

    logits : ``[S, W, V]`` f32 — row ``i`` of slot ``s`` is the
        target's distribution for the token FOLLOWING position
        ``pos[s] + i`` (row 0 scores the pending token's successor,
        row ``i >= 1`` scores the successor of draft ``i``).
    drafts : ``[S, K]`` int32 (``W = K + 1``) — draft column ``i`` is
        scored against logits row ``i``.
    n_draft : ``[S]`` int32, 0..K — real drafts per slot; rows past
        ``n_draft`` are padding and never accepted.
    keydata / temps : per-slot sampling state, same conventions as the
        decode core (temperature <= 0 means greedy).

    Returns ``(out_tokens [S, W], n_acc [S], new_keydata)``:
    ``out_tokens[s, :n_acc[s]]`` are the emitted tokens — the accepted
    draft prefix followed by one correction (greedy: the argmax at the
    first mismatch; sampled: the residual draw) or bonus token. Always
    ``1 <= n_acc <= n_draft + 1``. Key bookkeeping is fixed-shape:
    every slot advances its key exactly once per call regardless of
    acceptance, so replays are deterministic per seed."""
    S, W, V = logits.shape
    K = W - 1
    logits = logits.astype(jnp.float32)
    drafts = drafts.astype(jnp.int32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)      # [S, W]
    keys = jax.random.wrap_key_data(keydata)
    # 2K + 2 subkeys per slot: [carry, u_0..u_{K-1}, r_0..r_{K-1},
    # bonus] — a FIXED split schedule, so acceptance patterns never
    # perturb later randomness
    nk = jax.vmap(lambda kk: jax.random.split(kk, 2 * K + 2))(keys)
    carry = nk[:, 0]
    safe_t = jnp.where(temps > 0, temps, 1.0)[:, None, None]
    scaled = logits / safe_t                                    # [S, W, V]
    # acceptance probability of draft i under a deterministic draft
    # distribution: min(1, p/q) with q = 1 at the draft token -> p(d_i)
    p = jax.nn.softmax(scaled[:, :K], axis=-1)                  # [S, K, V]
    p_draft = jnp.take_along_axis(p, drafts[..., None],
                                  axis=-1)[..., 0]              # [S, K]
    u = jax.vmap(jax.vmap(jax.random.uniform))(nk[:, 1:K + 1])  # [S, K]
    # residual distribution on rejection at i: p with d_i removed,
    # renormalized (categorical over masked logits does exactly that)
    hole = jax.nn.one_hot(drafts, V, dtype=bool)                # [S, K, V]
    residual = jnp.where(hole, -jnp.inf, scaled[:, :K])
    rej = jax.vmap(jax.vmap(jax.random.categorical))(
        nk[:, K + 1:2 * K + 1], residual).astype(jnp.int32)     # [S, K]
    # bonus draw from the row AFTER the last draft (row n_draft)
    bonus_row = jnp.take_along_axis(
        scaled, n_draft[:, None, None], axis=1)[:, 0]           # [S, V]
    bonus = jax.vmap(jax.random.categorical)(
        nk[:, 2 * K + 1], bonus_row).astype(jnp.int32)          # [S]
    idx = jnp.arange(K, dtype=jnp.int32)[None, :]
    ok_greedy = drafts == greedy[:, :K]
    ok_sample = u < p_draft
    ok = jnp.where((temps > 0)[:, None], ok_sample, ok_greedy) \
        & (idx < n_draft[:, None])
    # m = length of the leading accepted prefix (0..n_draft)
    m = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                axis=1).astype(jnp.int32)                       # [S]
    rej_m = jnp.take_along_axis(
        rej, jnp.clip(m, 0, max(K - 1, 0))[:, None], axis=1)[:, 0]
    greedy_m = jnp.take_along_axis(greedy, m[:, None], axis=1)[:, 0]
    corr_sampled = jnp.where(m < n_draft, rej_m, bonus)
    corr = jnp.where(temps > 0, corr_sampled, greedy_m)
    # out row: accepted drafts in columns < m, the correction at m
    # (columns past m are don't-care; fill with the correction)
    cols = jnp.arange(W, dtype=jnp.int32)[None, :]
    drafts_w = jnp.concatenate([drafts, drafts[:, :1]], axis=1) \
        if K else jnp.zeros((S, W), jnp.int32)
    out = jnp.where(cols < m[:, None], drafts_w, corr[:, None])
    return (out.astype(jnp.int32), (m + 1).astype(jnp.int32),
            jax.random.key_data(carry))


__all__ = ["SpecConfig", "NGramDraft", "accept_tokens"]
