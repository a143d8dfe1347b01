"""Step functions lowered by the launcher and the dry-run.

``make_train_step`` builds the GSPMD path: pure function, sharding comes
from in/out_shardings at jit time; XLA inserts FSDP all-gathers,
TP collectives and the DP/pod gradient all-reduce. Microbatch gradient
accumulation (``microbatches > 1``) runs as a ``lax.scan`` so activation
memory scales 1/m while the gradient all-reduce still happens ONCE per
step (it sits outside the scan) — this is the compute/communication
overlap story: per-microbatch compute overlaps the previous microbatch's
FSDP gathers under XLA's latency-hiding scheduler.

``make_train_step_explicit`` is the shard_map variant with hand-placed
collectives, used to demonstrate int8 cross-pod gradient compression
(repro.optim.compress) — per-tensor psum over "data" in fp32, int8 over
"pod".
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models import loss_fn
from repro.optim.adamw import AdamWHyper, adamw_update, clip_by_global_norm


def _split_micro(batch, m: int):
    def sp(x):
        b = x.shape[0]
        assert b % m == 0, (b, m)
        return x.reshape(m, b // m, *x.shape[1:])

    return jax.tree.map(sp, batch)


def make_train_step(cfg, hyper: AdamWHyper | None = None, microbatches: int = 1, lr_fn=None):
    hyper = hyper or AdamWHyper()

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, cfg, batch), has_aux=True
            )(params)
        else:
            micro = _split_micro(batch, microbatches)

            def acc_step(carry, mb):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(
                    lambda p: loss_fn(p, cfg, mb), has_aux=True
                )(params)
                gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gacc, g)
                return (gacc, lacc + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(acc_step, (g0, 0.0), micro)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
        grads, gnorm = clip_by_global_norm(grads, hyper.grad_clip)
        lr = lr_fn(opt_state["step"]) if lr_fn is not None else None
        params, opt_state = adamw_update(grads, opt_state, params, hyper, lr=lr)
        out = {"loss": loss, "grad_norm": gnorm}
        return params, opt_state, out

    return train_step


def make_train_step_explicit(cfg, mesh, hyper: AdamWHyper | None = None, compress_pod: bool = True):
    """shard_map step with explicit collectives + int8 pod-hop compression.

    Batch is sharded over (pod, data); params/opt are REPLICATED within
    the shard_map body (the GSPMD path owns FSDP; this path exists to
    place the gradient reduction by hand). Gradients: psum over "data"
    (fp32, ICI) then error-feedback int8 psum over "pod" (DCN).
    """
    from jax.sharding import PartitionSpec as P

    from repro.optim.compress import allreduce_int8

    hyper = hyper or AdamWHyper()
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    has_pod = "pod" in mesh.axis_names

    def body(params, opt_state, err, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch), has_aux=True
        )(params)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "data"), grads)
        if has_pod:
            if compress_pod:
                grads, err = allreduce_int8(grads, err, "pod")
                grads = jax.tree.map(lambda g: g, grads)
            else:
                grads = jax.tree.map(lambda g: jax.lax.pmean(g, "pod"), grads)
        loss = jax.lax.pmean(loss, "data")
        if has_pod:
            loss = jax.lax.pmean(loss, "pod")
        grads, gnorm = clip_by_global_norm(grads, hyper.grad_clip)
        params, opt_state = adamw_update(grads, opt_state, params, hyper)
        return params, opt_state, err, {"loss": loss, "grad_norm": gnorm}

    def step(params, opt_state, err, batch):
        batch_specs = jax.tree.map(lambda x: P(dp, *(None,) * (x.ndim - 1)), batch)
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P(), params),
                jax.tree.map(lambda _: P(), opt_state),
                jax.tree.map(lambda _: P(), err),
                batch_specs,
            ),
            out_specs=(
                jax.tree.map(lambda _: P(), params),
                jax.tree.map(lambda _: P(), opt_state),
                jax.tree.map(lambda _: P(), err),
                {"loss": P(), "grad_norm": P()},
            ),
            check_vma=False,
        )(params, opt_state, err, batch)

    return step


def make_prefill_step(cfg, max_len: int | None = None):
    from repro.models import prefill

    def prefill_step(params, batch):
        s = batch["tokens"].shape[1] + (cfg.n_patches or 0)
        return prefill(params, cfg, batch, max_len or s)

    return prefill_step


def make_decode_step(cfg):
    from repro.models import decode_step

    def serve_step(params, cache, tokens):
        return decode_step(params, cfg, cache, tokens)

    return serve_step
