"""Jittable train / serve step builders shared by the launchers and dry-run.

``make_train_step`` — forward+backward+AdamW with microbatch gradient
accumulation (lax.scan). ``grad_dtype='bfloat16'`` halves the wire format of
the implicit gradient all-reduces (accumulation stays correct through the
f32 optimizer). The stronger error-feedback int8 compression lives in
:mod:`repro.runtime.compress` as an explicit shard_map collective — it
applies when the pod axis is reduced manually (DiLoCo-style local gradients
per pod), which is a deployment choice the launcher exposes rather than a
default: synchronous GSPMD jobs keep the implicit all-reduce.

``make_serve_step`` — one-token greedy decode against the KV/SSM caches; runs
with float or OCS-quantized (int8) parameter trees interchangeably.

``make_prefill_step`` — full-sequence forward (inference prefill).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.optim import adamw_update, cosine_schedule, global_norm

__all__ = ["TrainHyper", "make_train_step", "make_serve_step", "make_prefill_step"]


@dataclasses.dataclass(frozen=True)
class TrainHyper:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    n_micro: int = 1  # gradient-accumulation microbatches
    grad_dtype: str = "float32"  # 'bfloat16' -> compressed grad collectives
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def _tree_add(a, b):
    return jax.tree.map(lambda x, y: x + y, a, b)


def make_train_step(cfg: ModelConfig, hyper: TrainHyper):
    gdt = jnp.bfloat16 if hyper.grad_dtype == "bfloat16" else jnp.float32

    def train_step(params, opt_state, batch):
        def loss_of(p, mb):
            return T.loss_fn(p, mb, cfg)

        if hyper.n_micro > 1:
            micro = jax.tree.map(
                lambda x: x.reshape((hyper.n_micro, -1) + x.shape[1:]), batch
            )

            def body(carry, mb):
                gsum, lsum = carry
                li, gi = jax.value_and_grad(loss_of)(params, mb)
                gi = jax.tree.map(lambda g: g.astype(gdt), gi)
                return (_tree_add(gsum, gi), lsum + li), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, gdt), params)
            (grads, lsum), _ = jax.lax.scan(body, (g0, jnp.zeros((), jnp.float32)), micro)
            scale = 1.0 / hyper.n_micro
            grads = jax.tree.map(lambda g: (g.astype(gdt) * gdt(scale)), grads)
            loss = lsum * scale
        else:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
            grads = jax.tree.map(lambda g: g.astype(gdt), grads)

        lr = cosine_schedule(opt_state.count, hyper.lr, hyper.warmup, hyper.total_steps)
        gnorm = global_norm(grads)
        new_params, new_opt = adamw_update(
            grads,
            opt_state,
            params,
            lr=lr,
            weight_decay=hyper.weight_decay,
            clip_norm=hyper.clip_norm,
        )
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return new_params, new_opt, metrics

    return train_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, caches, token):
        """token: [B, 1] -> (next_token [B, 1], logits [B, V], new caches)."""
        logits, new_caches = T.decode_step(T.per_layer(params), token, caches, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, new_caches

    return serve_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        logits = T.forward(
            params, batch.get("tokens"), cfg, embeds=batch.get("embeds")
        )
        return logits[:, -1, :]

    return prefill_step
