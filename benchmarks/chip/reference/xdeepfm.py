"""xDeepFM (arXiv 1803.05170) as the plain reference computes it.

The deep MLP over the dense features and the flattened bags, plus a
compressed interaction network (CIN): each layer takes the outer product of
its maps with the field embeddings along the embedding dimension and
compresses it with a (maps, fields, maps') weight; the sums over D of every
layer's maps feed a linear output. No wide pool.
"""
import jax
import jax.numpy as jnp

import refcore


def init(key, sizes):
    D, T = sizes["embed_dim"], sizes["n_tables"]
    maps = list(sizes["cin_layers"])
    k_tab, k_mlp, k_cin = jax.random.split(key, 3)
    keys = jax.random.split(k_cin, len(maps) + 1)
    cin, prev = {}, T
    for li, n in enumerate(maps):
        cin[f"w{li}"] = refcore.normal(keys[li], (prev, T, n), prev * T)
        prev = n
    cin["w_out"] = refcore.normal(keys[-1], (sum(maps),), sum(maps))
    return {
        "tables": refcore.normal(k_tab, (sum(sizes["table_rows"]), D), D),
        "mlp": refcore.mlp_init(k_mlp, sizes["mlp_dims"],
                                sizes["n_dense"] + T * D),
        "cin": cin,
    }


def logit(params, dense, embs):
    emb = embs["deep"]                                   # (B, T, D)
    xk, feats = emb, []
    for li in range(len(params["cin"]) - 1):
        inter = jnp.einsum("bhd,bmd->bhmd", xk, emb)
        xk = jnp.einsum("bhmd,hmn->bnd", inter, params["cin"][f"w{li}"])
        feats.append(jnp.sum(xk, axis=-1))
    cin_out = jnp.concatenate(feats, axis=-1) @ params["cin"]["w_out"]
    return refcore.mlp_apply(params["mlp"], dense, emb) + cin_out
