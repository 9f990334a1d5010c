"""Plain float32 forward pass of a dense block, and the check of served tokens
against it.

The block as the served configuration runs it: RMSNorm (unit scales), RoPE
over the whole head (the two halves rotate together), causal attention with
grouped K/V heads (query head ``j`` reads K/V head ``j // (H / KV)``) scaled
by ``hd ** -0.5``, and a SwiGLU feed-forward ``(silu(h Wg) * (h Wu)) Wd``;
a final RMSNorm and the output head. Every product runs in float32 at
``highest`` precision, on the float32 weights the seed stands for
(:mod:`bench.reference.weights`), one layer at a time: a layer's weights
are drawn on the host while the chip runs the one before.

The control is the same pass with every matrix but the embedding rounded to
int4 (symmetric, one scale per output column): the precision below the
int8 weights the configuration serves.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

SEQ_PAD = 1024  # sequences are padded to a multiple: few shapes, all cached
Q_CHUNK = 512  # queries per attention block
ROWS = 512  # logit rows per sequence, padded: the longest answer fits


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "eps"))
def _layer(x, w, *, heads, kv_heads, theta, eps):
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        hd = w["wq"].shape[1] // heads
        rep = heads // kv_heads
        h = _rms(x, eps)
        q = _rope((h @ w["wq"]).reshape(s, heads, hd), theta) * hd ** -0.5
        k = _rope((h @ w["wk"]).reshape(s, kv_heads, hd), theta)
        v = (h @ w["wv"]).reshape(s, kv_heads, hd)
        n = s // Q_CHUNK
        qc = q.reshape(n, Q_CHUNK, kv_heads, rep, hd)
        kpos = jnp.arange(s)

        def block(args):
            qb, start = args
            sc = jnp.einsum("qgrd,kgd->qgrk", qb, k)
            qpos = start + jnp.arange(Q_CHUNK)
            sc = jnp.where(kpos[None, None, None, :] <= qpos[:, None, None, None],
                           sc, -jnp.inf)
            return jnp.einsum("qgrk,kgd->qgrd", jax.nn.softmax(sc, axis=-1), v)

        att = jax.lax.map(block, (qc, jnp.arange(n) * Q_CHUNK))
        x = x + att.reshape(s, heads * hd) @ w["wo"]
        h = _rms(x, eps)
        return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, eps) @ head


@jax.jit
def _int4(w):
    """Round to int4, symmetric, one scale per output column."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(w / s), -7, 7) * s


def _gap(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the row's best, in
    units of the row's standard deviation over the vocabulary."""
    best = ref.max(axis=1)
    got = ref[np.arange(len(tokens)), tokens]
    return (best - got) / ref.std(axis=1)


def served_gaps(model: Dict, seed: int, seqs: Sequence[Tuple[np.ndarray, Sequence[int]]],
                *, control: bool = False, device=None,
                threads: int = 0) -> Dict[str, List[np.ndarray]]:
    """For each ``(prompt, served tokens)``: the gap of every served token
    below the reference's best at its position, the reference fed the
    prompt and the served tokens before it. With ``control``, also the gap
    of the token that the int4 control puts first at each position."""
    device = device if device is not None else jax.devices()[0]
    threads = threads or len(os.sched_getaffinity(0))
    kw = dict(heads=model["n_heads"], kv_heads=model["n_kv_heads"],
              theta=float(model.get("rope_theta", 10000.0)),
              eps=float(model.get("norm_eps", 1e-6)))
    eps = kw["eps"]
    layers = model["n_layers"]
    put = lambda a: jax.device_put(a, device)

    with ThreadPoolExecutor(threads) as pool:
        head_f = pool.submit(W.lm_head, model, seed)
        table = W.embed(model, seed)

        def draw(layer):
            return {n: pool.submit(W.layer_matrix, model, seed, layer, n)
                    for n in W.MATRICES}

        pending = {i: draw(i) for i in range(min(2, layers))}
        xs = []
        for prompt, served in seqs:
            toks = np.concatenate([np.asarray(prompt, np.int64),
                                   np.asarray(served[:-1], np.int64)])
            padded = np.zeros(-(-len(toks) // SEQ_PAD) * SEQ_PAD, np.int64)
            padded[:len(toks)] = toks
            xs.append(put(table[padded]))
        del table
        xc = list(xs)
        for i in range(layers):
            w = {n: put(f.result()) for n, f in pending.pop(i).items()}
            if i + 2 < layers:
                pending[i + 2] = draw(i + 2)
            xs = [_layer(x, w, **kw) for x in xs]
            if control:
                w4 = {n: _int4(a) for n, a in w.items()}
                xc = [_layer(x, w4, **kw) for x in xc]
                del w4
            jax.block_until_ready((xs, xc))
            del w
        head = put(head_f.result())
        head4 = _int4(head) if control else None

    out: Dict[str, List[np.ndarray]] = {"served": [], "control": []}
    for (prompt, served), x, y in zip(seqs, xs, xc):
        n0, m = len(prompt) - 1, len(served)
        rows = lambda a: put(np.pad(np.asarray(a[n0:n0 + m]), ((0, ROWS - m), (0, 0))))
        ref = np.asarray(_logits(rows(x), head, eps=eps))[:m]
        out["served"].append(_gap(ref, np.asarray(served)))
        if control:
            ctl = np.asarray(_logits(rows(y), head4, eps=eps))[:m]
            out["control"].append(_gap(ref, ctl.argmax(axis=1)))
    return out
