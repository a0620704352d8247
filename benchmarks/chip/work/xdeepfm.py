"""Operations and bytes of one ``xdeepfm`` training step, from its shapes.

The forward's matmuls are the deep MLP and the CIN: per layer an outer
product of its maps with the field embeddings (an einsum that sums nothing:
2 per product, as ``flops_of`` counts it) and a compression over maps and
fields, then the linear output over every layer's maps. A training step
is 3 times the forward.
"""
import workcore


def cin_fwd_flops(sizes) -> float:
    B, T, D = sizes["batch_size"], sizes["n_tables"], sizes["embed_dim"]
    total, prev = 0.0, T
    for maps in sizes["cin_layers"]:
        total += 2.0 * B * prev * T * D              # outer product
        total += 2.0 * B * D * prev * T * maps       # compression
        prev = maps
    return total + 2.0 * B * sum(sizes["cin_layers"])


def fwd_flops(sizes) -> float:
    return workcore.mlp_fwd_flops(sizes) + cin_fwd_flops(sizes)


def step_flops(sizes) -> float:
    return 3.0 * fwd_flops(sizes)


def pool_dims(sizes):
    """Row width of each pooled store the forward looks up: tables."""
    return [sizes["embed_dim"]]
