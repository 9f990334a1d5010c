"""Plain float32 forward pass of a fine-grained MoE block with shared
experts (DeepSeek-MoE), and the check of served tokens against it.

The block as the served configuration runs it:

* attention exactly as :mod:`bench.reference.dense` has it (RMSNorm, RoPE
  over the whole head, causal attention in query blocks) — its ``_layer``
  with a feed-forward of width 0 is the attention half of the block;
* the leading ``moe.first_dense`` layers: a SwiGLU of ``d_ff``
  (``dense._layer`` whole);
* every other layer: a softmax router over all ``moe.n_experts`` (float32,
  never quantized), the top ``moe.top_k`` experts per token, their gates
  renormalised only where ``moe.norm_topk``; the routed output summed over
  the experts held here, ``[0, moe.n_held)``, each a SwiGLU of
  ``moe.expert_ff``; plus the shared experts, one SwiGLU of ``n_shared *
  expert_ff``, counted once. What the absent experts would add is left
  out, as the program leaves it out.

Weights are drawn by :func:`bench.reference.weights.draw` with this block's
sorted leaf list (:data:`MOE_LEAVES`): an expert ``e`` of moe layer ``l`` at
slice ``l * n_experts + e``, with the published ``n_experts``, so the held
experts are the draws an uncut model would have. Every product runs in
float32 at ``highest`` precision; layers are drawn on the host while the
chip runs the one before.

The control is the same pass with every matrix the program quantizes (all
but the embedding and the router) rounded to int4, one scale per output
column: the precision below the int8 weights the configuration serves.
"""
from __future__ import annotations

import functools
import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dense as D
from . import weights as W

# The moe block's leaves in the order the tree flattens them.
MOE_LEAVES = (
    "embed", "final_norm/scale",
    "layers/attn/wk", "layers/attn/wo", "layers/attn/wq", "layers/attn/wv",
    "layers/mlp/w_down", "layers/mlp/w_gate", "layers/mlp/w_up",
    "layers/moe/experts/w_down", "layers/moe/experts/w_gate",
    "layers/moe/experts/w_up", "layers/moe/router",
    "layers/moe/shared/w_down", "layers/moe/shared/w_gate",
    "layers/moe/shared/w_up",
    "layers/norm1/scale", "layers/norm2/scale",
    "lm_head",
)
ATTN = ("wq", "wk", "wv", "wo")
FFN = ("w_gate", "w_up", "w_down")


def _check(model: Dict) -> None:
    if (model["block"], model["act"], model["norm"]) != ("moe", "swiglu", "rms"):
        raise ValueError("the reference covers moe SwiGLU RMSNorm blocks only")
    if model.get("tie_embeddings") or model.get("qk_norm"):
        raise ValueError("tied embeddings and qk-norm are not in the reference")
    if not model["moe"].get("n_shared"):
        raise ValueError("the reference's leaf list has shared experts")


def _ffn_shape(name: str, d: int, f: int) -> Tuple[int, int]:
    return (f, d) if name == "w_down" else (d, f)


def _draw(model: Dict, seed: int, leaf: str, i: int, shape) -> np.ndarray:
    return W.draw(seed, MOE_LEAVES, leaf, i, shape, 1.0 / math.sqrt(shape[0]))


def draw_layer(model: Dict, seed: int, layer: int, pool) -> Dict:
    """Futures of one layer's float32 matrices: ``wq`` ... ``wo``, then
    ``w_gate`` ... ``w_down`` of a dense layer, or ``router``, ``experts``
    (each a list over the held experts) and ``shared`` of a moe layer."""
    _check(model)
    m, d = model["moe"], model["d_model"]
    fd = m.get("first_dense", 0)
    out = {n: pool.submit(_draw, model, seed, f"layers/attn/{n}", layer,
                          W.matrix_shape(model, n)) for n in ATTN}
    if layer < fd:
        for n in FFN:
            out[n] = pool.submit(_draw, model, seed, f"layers/mlp/{n}", layer,
                                 _ffn_shape(n, d, model["d_ff"]))
        return out
    l = layer - fd
    held = m.get("n_held") or m["n_experts"]
    out["router"] = pool.submit(_draw, model, seed, "layers/moe/router", l,
                                (d, m["n_experts"]))
    for n in FFN:
        out[f"experts/{n}"] = [
            pool.submit(_draw, model, seed, f"layers/moe/experts/{n}",
                        l * m["n_experts"] + e, _ffn_shape(n, d, m["expert_ff"]))
            for e in range(held)]
        out[f"shared/{n}"] = pool.submit(
            _draw, model, seed, f"layers/moe/shared/{n}", l,
            _ffn_shape(n, d, m["n_shared"] * m["expert_ff"]))
    return out


@functools.partial(jax.jit, static_argnames=("k", "norm", "eps"))
def _moe(x, w, *, k, norm, eps):
    """x plus the routed output of the held experts and the shared experts."""
    with jax.default_matmul_precision("highest"):
        h = D._rms(x, eps)
        gate, idx = jax.lax.top_k(jax.nn.softmax(h @ w["router"], axis=-1), k)
        if norm:
            gate = gate / gate.sum(-1, keepdims=True)
        ex = w["experts"]
        held = ex["w_gate"].shape[0]
        # [s, held]: each token's gate on each held expert (0 if not routed)
        g = jnp.sum(jnp.where(idx[:, :, None] == jnp.arange(held), gate[:, :, None],
                              0.0), axis=1)

        def expert(y, we_ge):
            we, ge = we_ge
            out = (jax.nn.silu(h @ we["w_gate"]) * (h @ we["w_up"])) @ we["w_down"]
            return y + ge[:, None] * out, None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (ex, g.T))
        sh = w["shared"]
        y = y + (jax.nn.silu(h @ sh["w_gate"]) * (h @ sh["w_up"])) @ sh["w_down"]
        return x + y


def _passes(model: Dict, seed: int, seqs: Sequence[np.ndarray], *,
            control: bool, device, threads: int):
    """Each token sequence through every layer: the final hidden states of
    the reference (and of the int4 control), and the two heads."""
    _check(model)
    threads = threads or len(os.sched_getaffinity(0))
    m = model["moe"]
    kw = dict(heads=model["n_heads"], kv_heads=model["n_kv_heads"],
              theta=float(model.get("rope_theta", 10000.0)),
              eps=float(model.get("norm_eps", 1e-6)))
    moe_kw = dict(k=m["top_k"], norm=bool(m.get("norm_topk", True)), eps=kw["eps"])
    layers = model["n_layers"]
    d = model["d_model"]
    put = lambda a: jax.device_put(a, device)
    # The attention half of a moe layer: dense._layer with a feed-forward
    # of width 0 adds exactly nothing after the attention.
    no_ffn = {n: put(np.zeros(_ffn_shape(n, d, 0), np.float32)) for n in FFN}
    int4 = lambda a: jax.vmap(D._int4)(a) if a.ndim == 3 else D._int4(a)

    def run(x, w):
        if "router" not in w:
            return D._layer(x, w, **kw)
        x = D._layer(x, {**{n: w[n] for n in ATTN}, **no_ffn}, **kw)
        return _moe(x, w, **moe_kw)

    def rounded(w):
        return {n: (a if n == "router" else jax.tree.map(int4, a))
                for n, a in w.items()}

    with ThreadPoolExecutor(threads) as pool:
        head_f = pool.submit(_draw, model, seed, "lm_head", 0, (d, model["vocab"]))
        table = W.draw(seed, MOE_LEAVES, "embed", 0, (model["vocab"], d), 0.02)
        pending = {i: draw_layer(model, seed, i, pool) for i in range(min(2, layers))}
        xs = []
        for toks in seqs:
            padded = np.zeros(-(-len(toks) // D.SEQ_PAD) * D.SEQ_PAD, np.int64)
            padded[:len(toks)] = toks
            xs.append(put(table[padded]))
        del table
        xc = list(xs)
        for i in range(layers):
            w = _nest({n: put(np.stack([x.result() for x in f]) if isinstance(f, list)
                              else f.result())
                       for n, f in pending.pop(i).items()})
            if i + 2 < layers:
                pending[i + 2] = draw_layer(model, seed, i + 2, pool)
            xs = [run(x, w) for x in xs]
            if control:
                w4 = rounded(w)
                xc = [run(x, w4) for x in xc]
                del w4
            jax.block_until_ready((xs, xc))
            del w
        head = put(head_f.result())
    return xs, xc, head, (D._int4(head) if control else None)


def _rows_logits(x, head, n0: int, k: int, eps: float, device) -> np.ndarray:
    """The logits of rows ``[n0, n0 + k)`` of hidden states ``x``."""
    rows = jax.device_put(
        np.pad(np.asarray(x[n0:n0 + k]), ((0, D.ROWS - k), (0, 0))), device)
    return np.asarray(D._logits(rows, head, eps=eps))[:k]


def logits(model: Dict, seed: int, tokens: Sequence[int], *, device=None,
           threads: int = 0) -> np.ndarray:
    """The reference's logits at every position of ``tokens`` (at most
    :data:`bench.reference.dense.ROWS` of them), float32 ``[n, vocab]``."""
    device = device if device is not None else jax.devices()[0]
    xs, _, head, _ = _passes(model, seed, [np.asarray(tokens, np.int64)],
                             control=False, device=device, threads=threads)
    return _rows_logits(xs[0], head, 0, len(tokens),
                        float(model.get("norm_eps", 1e-6)), device)


def served_gaps(model: Dict, seed: int, seqs: Sequence[Tuple[np.ndarray, Sequence[int]]],
                *, control: bool = False, device=None,
                threads: int = 0) -> Dict[str, List[np.ndarray]]:
    """For each ``(prompt, served tokens)``: the gap of every served token
    below the reference's best at its position, the reference fed the
    prompt and the served tokens before it. With ``control``, also the gap
    of the token that the int4 control puts first at each position."""
    device = device if device is not None else jax.devices()[0]
    toks = [np.concatenate([np.asarray(p, np.int64), np.asarray(s[:-1], np.int64)])
            for p, s in seqs]
    xs, xc, head, head4 = _passes(model, seed, toks, control=control,
                                  device=device, threads=threads)
    eps = float(model.get("norm_eps", 1e-6))
    out: Dict[str, List[np.ndarray]] = {"served": [], "control": []}
    for (prompt, served), x, y in zip(seqs, xs, xc):
        n0, k = len(prompt) - 1, len(served)
        ref = _rows_logits(x, head, n0, k, eps, device)
        out["served"].append(D._gap(ref, np.asarray(served)))
        if control:
            ctl = _rows_logits(y, head4, n0, k, eps, device)
            out["control"].append(D._gap(ref, ctl.argmax(axis=1)))
    return out


def _nest(w: Dict) -> Dict:
    """``experts/w_gate``-style keys as nested ``experts`` and ``shared``."""
    out: Dict = {}
    for n, a in w.items():
        if "/" in n:
            group, leaf = n.split("/")
            out.setdefault(group, {})[leaf] = a
        else:
            out[n] = a
    return out
