"""Wide & Deep (arXiv 1606.07792) as the plain reference computes it.

Weights: the pooled tables (R, D), the deep MLP over the dense features and
the flattened bags, the wide pool (R, 1) summed over every lookup, and a
linear term on the dense features. The benchmark makes the program's
initial state and the reference's weights with this ``init``.
"""
import jax
import jax.numpy as jnp

import refcore


def init(key, sizes):
    R = sum(sizes["table_rows"])
    D, T, H = sizes["embed_dim"], sizes["n_tables"], sizes["multi_hot"]
    k_tab, k_mlp, k_wide, k_wd = jax.random.split(key, 4)
    return {
        "tables": refcore.normal(k_tab, (R, D), D),
        "mlp": refcore.mlp_init(k_mlp, sizes["mlp_dims"],
                                sizes["n_dense"] + T * D),
        "wide": refcore.normal(k_wide, (R, 1), T * H),
        "wide_dense": refcore.normal(k_wd, (sizes["n_dense"],),
                                     sizes["n_dense"]),
    }


def logit(params, dense, embs):
    wide = dense @ params["wide_dense"] + jnp.sum(embs["wide"][..., 0], axis=1)
    return refcore.mlp_apply(params["mlp"], dense, embs["deep"]) + wide
