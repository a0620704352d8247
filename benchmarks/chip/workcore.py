"""Operations and bytes shared by the model kinds' files under ``work/``.

FLOPs count 2 per multiply-add, as ``repro.launch.costs.flops_of`` counts a
``dot_general``. Bytes are the least HBM traffic the operation needs,
whatever its implementation.
"""


def mlp_fwd_flops(sizes) -> float:
    """The deep MLP over the dense features and the flattened bags."""
    d_in = sizes["n_dense"] + sizes["n_tables"] * sizes["embed_dim"]
    macs, prev = 0, d_in
    for h in list(sizes["mlp_dims"]) + [1]:
        macs += prev * h
        prev = h
    return 2.0 * sizes["batch_size"] * macs


def emb_fwd_bytes(sizes, dim: int) -> float:
    """One pooled lookup of rows of width ``dim``: the int32 ids, every
    gathered f32 row once, and the pooled f32 output."""
    lookups = sizes["batch_size"] * sizes["n_tables"] * sizes["multi_hot"]
    return 4.0 * (lookups + lookups * dim
                  + sizes["batch_size"] * sizes["n_tables"] * dim)


def emb_fwd_flops(sizes, dim: int) -> float:
    """The bag sums: one add per gathered element."""
    return float(sizes["batch_size"] * sizes["n_tables"]
                 * sizes["multi_hot"] * dim)
