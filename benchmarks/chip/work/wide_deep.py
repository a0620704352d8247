"""Operations and bytes of one ``wide_deep`` training step, from its shapes.

The forward's matmuls are the deep MLP and the wide part's dense term; the
lookups are no matmuls. A training step is the forward and twice its work
in the backward: 3 times the forward.
"""
import workcore


def fwd_flops(sizes) -> float:
    return (workcore.mlp_fwd_flops(sizes)
            + 2.0 * sizes["batch_size"] * sizes["n_dense"])


def step_flops(sizes) -> float:
    return 3.0 * fwd_flops(sizes)


def pool_dims(sizes):
    """Row width of each pooled store the forward looks up: tables, wide."""
    return [sizes["embed_dim"], 1]
