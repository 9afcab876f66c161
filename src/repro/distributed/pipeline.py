"""Pipeline parallelism over the `pod` axis (GPipe schedule).

At ≥480B scale, pure DP across pods wastes the slow DCN hop on gradient
all-reduce of the full parameter set.  This module provides the
alternative: layers are partitioned into stages (one per pod), and
microbatches stream through a `shard_map`ed loop with
`lax.ppermute` stage-to-stage handoffs — the collective crossing DCN is
then one activation tensor per microbatch instead of all gradients.

``pipeline_apply`` is schedule-only and takes any per-stage function, so
the model zoo's scan-based stacks drop in unchanged (a stage closure
over ``_run_group``).  Bubble fraction = (S-1)/(M+S-1) for S stages and
M microbatches.

Self-check (8 host devices, 2 stages):

    REPRO_PP_DEVICES=8 python -m repro.distributed.pipeline
"""
from __future__ import annotations

if __name__ == "__main__":        # must precede the jax import below
    import os
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count="
                          + os.environ.get("REPRO_PP_DEVICES", "8"))

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, microbatches,
                   *, mesh, axis: str = "pod"):
    """Run ``microbatches`` [M, ...] through all pipeline stages.

    ``stage_params``: pytree with a leading stage axis (sharded over
    ``axis``); ``stage_fn(params_slice, x) -> y`` applies one stage.
    Returns outputs [M, ...] (valid on every device after the final
    broadcast).
    """
    n_stages = mesh.shape[axis]
    M = microbatches.shape[0]

    def inner(params_local, mb):
        # params_local leaves: [1, ...] (this stage's slice); mb: [M, ...]
        idx = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params_local)
        T = M + n_stages - 1

        def step(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (clamped when past the end)
            inj = jnp.minimum(t, M - 1)
            x0 = jax.lax.dynamic_index_in_dim(mb, inj, 0, keepdims=False)
            x_in = jnp.where(idx == 0, x0, buf)
            y = stage_fn(p, x_in)
            # hand off to the next stage (ring; last->0 ignored)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            # the last stage's result for microbatch (t - n_stages + 1)
            out_t = jnp.clip(t - (n_stages - 1), 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, out_t, 0,
                                               keepdims=False)
            write = (idx == n_stages - 1) & (t >= n_stages - 1)
            upd = jnp.where(write, y, cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, out_t, 0)
            return buf, outs

        buf0 = jnp.zeros_like(mb[0])
        outs0 = jnp.zeros_like(mb)
        _, outs = jax.lax.fori_loop(0, T, step, (buf0, outs0))
        # broadcast final outputs from the last stage to every stage
        if n_stages > 1:
            outs = jax.lax.all_gather(outs, axis)[n_stages - 1]
        return outs

    pspec = P(axis)
    # replication checking is off: the final all-gather makes the output
    # replicated, but the checker cannot prove it
    out = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: pspec, stage_params),
                  P()),
        out_specs=P(), check_vma=False,
    )(stage_params, microbatches)
    return out


def _self_check():
    import os
    import numpy as np
    from ..launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((2, 2, 2), ("pod", "data", "model"))

    # 4-layer MLP, 2 stages x 2 layers
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.standard_normal((4, 16, 16)) * 0.3, jnp.float32)

    def two_layers(w_pair, x):
        for i in range(2):
            x = jnp.tanh(x @ w_pair[i])
        return x

    stage_params = W.reshape(2, 2, 16, 16)       # [stages, 2, 16, 16]
    mb = jnp.asarray(rng.standard_normal((3, 8, 16)), jnp.float32)

    out = pipeline_apply(two_layers, stage_params, mb, mesh=mesh)

    ref = mb
    for i in range(4):
        ref = jnp.tanh(ref @ W[i])
    err = float(jnp.abs(out - ref).max())
    print(f"pipeline self-check max err: {err:.2e}")
    assert err < 1e-6
    # also prove it lowers with collective-permute on the pod axis
    lowered = jax.jit(lambda sp, m: pipeline_apply(
        two_layers, sp, m, mesh=mesh)).lower(stage_params, mb)
    txt = lowered.compile().as_text()
    assert "collective-permute" in txt
    print("HLO contains collective-permute: ok")


if __name__ == "__main__":
    _self_check()
