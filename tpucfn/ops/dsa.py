"""DeepSeek sparse attention's indexer (DeepSeek-V3.2-Exp): the scores that
choose each query's keys, the choice, and the KL loss that trains it.

With ``qi`` (B, HI, S, DI) the indexer's queries, ``ki`` (B, S, DI) its one
key head and ``w`` (B, S, HI) its per-head weights with every constant factor
in them:

* ``I[t, s] = Σ_j w[t, j] ReLU(qi[t, j] · ki[s])`` over causal pairs;
* ``S_t``: the ``min(topk, t + 1)`` causal keys with the largest
  ``I[t, ·]``, ties to the lower position (as ``lax.top_k`` orders them),
  carried as ``kernels/flash_attention.pack``'s words, and ``lse_i[t]``
  the log-sum-exp of ``I[t, S_t]``;
* ``KL_t = KL(p_t ‖ softmax(I[t, S_t]))`` with ``p_t`` the attention's
  probabilities over ``S_t`` averaged over its heads, read from its rows'
  log-sum-exp; :func:`kl` is the mean over rows, differentiable in ``qi``,
  ``ki`` and ``w`` alone.

On a TPU at lengths of whole blocks the kernels of ``kernels/dsa.py`` run
(``dsa_select``, ``dsa_kl_*``); elsewhere the ``jnp`` forms here, which
hold the (S, S) scores and serve the tests and the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpucfn.kernels import dsa as kernels
from tpucfn.kernels.flash_attention import pack, unpack

SELECT_BLOCKS = (128, 512)   # query rows, keys a step of ``dsa_select``
KL_BLOCK = 256               # query and key rows a tile of ``dsa_kl_*``


def _backend() -> str:
    return jax.default_backend()


def kernel_serves(s: int) -> bool:
    return _backend() == "tpu" and s % SELECT_BLOCKS[1] == 0 and s % KL_BLOCK == 0


def scores(qi, ki, w):
    """(B, S, S) float32 ``I``, ``-inf`` off the causal pairs."""
    a = jnp.einsum("bhtd,bsd->bhts", qi, ki, preferred_element_type=jnp.float32)
    i = jnp.einsum("bhts,bth->bts", jnp.maximum(a, 0.0), w)
    s = qi.shape[2]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    return jnp.where(causal, i, -jnp.inf)


def chosen(i, topk: int):
    """The selection of scores ``i`` (B, S, S) as a (B, S, S) bool mask."""
    b, s, _ = i.shape
    _, idx = lax.top_k(i, min(topk, s))
    mask = jnp.zeros((b, s, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None], idx].set(True)
    return mask & jnp.isfinite(i)


def select(qi, ki, w, *, topk: int):
    """(words, lse_i): the keys each query attends to and the log-sum-exp of
    their scores; no gradient flows through either."""
    qi, ki, w = (lax.stop_gradient(x) for x in (qi, ki, w))
    if kernel_serves(qi.shape[2]):
        bq, bk = SELECT_BLOCKS
        return kernels.select(qi, ki, w, topk=topk, block_q=bq, block_k=bk)
    i = scores(qi, ki, w)
    mask = chosen(i, topk)
    return pack(mask), jax.nn.logsumexp(jnp.where(mask, i, -jnp.inf), axis=-1)


def mean_probs(q, k, lse, mask, scale: float):
    """(B, S, S) the attention's probabilities over ``mask`` averaged over
    its heads; q, k (B, S, H, D), lse (B, S, H)."""
    logits = jnp.einsum("bthd,bshd->bhts", q, k,
                        preferred_element_type=jnp.float32) * scale
    p = jnp.exp(logits - jnp.swapaxes(lse, 1, 2)[..., None])
    return jnp.where(mask, jnp.mean(p, axis=1), 0.0)


def kl_rows(i, mask, p):
    """Each row's KL(p ‖ softmax of ``i`` over ``mask``), (B, S)."""
    log_q = i - jax.nn.logsumexp(jnp.where(mask, i, -jnp.inf), axis=-1,
                                 keepdims=True)
    safe = jnp.where(p > 0, p, 1.0)
    return jnp.sum(jnp.where(mask & (p > 0), p * (jnp.log(safe) - log_q), 0.0),
                   axis=-1)


def kl(qi, ki, w, words, lse_i, q, k, lse, *, scale: float):
    """The mean over rows of ``KL_t``; q, k (B, S, H, D) and lse (B, S, H)
    are the attention's, held as readings (no gradient reaches them)."""
    q, k, lse, words, lse_i = (lax.stop_gradient(x)
                               for x in (q, k, lse, words, lse_i))
    s = qi.shape[2]
    if kernel_serves(s):
        return kl_kernels(qi, ki, w, words, lse_i, jnp.swapaxes(q, 1, 2),
                          jnp.swapaxes(k, 1, 2), lse, scale, KL_BLOCK, False)
    mask = unpack(words, s)
    p = mean_probs(q, k, lse, mask, scale)
    return jnp.mean(kl_rows(scores(qi, ki, w), mask, p))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def kl_kernels(qi, ki, w, words, lse_i, q, k, lse, scale, block, interpret):
    """:func:`kl` through the kernels: q, k (B, H, S, D)."""
    rows = kernels.kl_rows(qi, ki, w, q, k, lse, lse_i, words, scale=scale,
                           block=block, grads=False, interpret=interpret)
    return jnp.mean(rows)


def _kl_fwd(qi, ki, w, words, lse_i, q, k, lse, scale, block, interpret):
    value = kl_kernels(qi, ki, w, words, lse_i, q, k, lse, scale, block,
                       interpret)
    return value, (qi, ki, w, words, lse_i, q, k, lse)


def _kl_bwd(scale, block, interpret, res, g):
    qi, ki, w, words, lse_i, q, k, lse = res
    args = (qi, ki, w, q, k, lse, lse_i, words)
    dqi, dw = kernels.kl_rows(*args, scale=scale, block=block, grads=True,
                              interpret=interpret)
    dki = kernels.kl_keys(*args, scale=scale, block=block, interpret=interpret)
    c = g / (qi.shape[0] * qi.shape[2])          # the mean over rows
    zero = lambda x: jnp.zeros_like(x)  # noqa: E731
    return ((c * dqi).astype(qi.dtype), (c * dki).astype(ki.dtype),
            (c * dw).astype(w.dtype),
            np.zeros(words.shape, jax.dtypes.float0), zero(lse_i),
            zero(q), zero(k), zero(lse))


kl_kernels.defvjp(_kl_fwd, _kl_bwd)
