"""Pipeline parallelism: GPipe-style microbatch rotation in shard_map.

The PP strategy for the multichip story (SURVEY §2.11 checklist): layer
stages are sharded over a ``pipe`` mesh axis; microbatches stream
through stages with ``lax.ppermute`` carrying activations to the next
stage each step (the scaling-book shard_map pipeline recipe — the
collectives ride ICI neighbors, exactly what ``ppermute`` lowers to).

The schedule is the classic GPipe fill-drain: with S stages and M
microbatches, the loop runs S-1+M steps; stage s computes on step t
when ``0 <= t - s < M``. Everything is static shapes inside one jit.
"""

from __future__ import annotations

PIPE_AXIS = "pipe"


def pipeline_apply(stage_fn, stage_params, x_microbatches, *, mesh,
                   axis: str = PIPE_AXIS):
    """Run microbatches through pipeline stages.

    - ``stage_fn(params, x) -> x``: one stage's compute (same shape in
      and out — e.g. a block of transformer layers).
    - ``stage_params``: pytree whose leaves have a leading stage dim of
      size S, sharded over ``axis`` (one slice per device).
    - ``x_microbatches``: (M, ...) microbatches, replicated.

    Returns (M, ...) outputs after all S stages.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis]
    n_micro = x_microbatches.shape[0]
    steps = n_stages - 1 + n_micro

    param_spec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    def stage_body(params, xs):
        # inside shard_map: leading stage dim is THIS device's slice
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)

        def step(carry, t):
            buf, outs = carry  # buf: activation entering this stage
            # stage 0 feeds itself from the microbatch stream
            feed = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage == 0,
                             xs[feed], buf)
            active = jnp.logical_and(t - stage >= 0,
                                     t - stage < n_micro)
            y = jnp.where(active, stage_fn(params, x_in), x_in)
            # the LAST stage writes its finished microbatch out
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            write = jnp.logical_and(stage == n_stages - 1, active)
            outs = jax.lax.cond(
                write,
                lambda o: o.at[out_idx].set(y),
                lambda o: o,
                outs)
            # rotate activations to the next stage over ICI neighbors
            nxt = jax.lax.ppermute(
                y, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (final_buf, outs), _ = jax.lax.scan(
            step, (buf0, outs0), jnp.arange(steps))
        # only the last stage wrote finished microbatches; psum over the
        # pipe axis replicates them to every stage (out_specs says the
        # result is replicated — without this, rank 0's zeros win)
        return jax.lax.psum(outs, axis)

    fn = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(param_spec, P()),
        out_specs=P(), check_vma=False)
    return fn(stage_params, x_microbatches)
