"""The paper's DLRM workloads: Wide&Deep (Model-X), xDeepFM (Model-Y), DCN (Model-Z).

Sparse categorical features -> embedding tables -> pooled lookups (the
paper's 30–48 % hot spot) -> dense interaction network -> CTR logit.

All ``n_tables`` embedding tables live in ONE pooled ``(sum(rows), D)``
array addressed through static per-table row offsets (``cfg.table_offsets``),
and the whole forward issues exactly one ``ops.fused_embedding_bag`` call for
the deep part (plus one for the wide part in wide_deep) instead of a Python
loop of per-table kernels. The pooled rows are sharded over the "model"
(parameter-server) axis, exactly as §2.1 describes — one spec covers every
table.

With a ``layout`` (a ``repro.sharding.policy.PaddedLayout``) the pooled
store is instead the padded ``(n_ps, max_range, D)`` array whose leading
axis GSPMD splits equally — physically-unequal PS shards materializing the
balanced range plan exactly (see ``docs/EMBEDDING_LAYOUT.md``). Values are
identical to the flat layout bit for bit; only where rows live changes.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.dlrm_models import DLRMConfig
from repro.kernels import ops
from repro.models.common import KeyGen, dense_init
from repro.sharding.policy import constrain, current_policy
from repro.spans import scope


def init_dlrm(cfg: DLRMConfig, key, layout=None) -> Dict[str, Any]:
    """Initialize DLRM params; ``layout`` pads the pooled stores physically.

    Args:
      cfg:    the DLRM workload config.
      key:    PRNG key.
      layout: optional ``PaddedLayout``; the pooled row arrays ("tables" and
              the wide part) come back as ``(n_ps, max_range, ...)`` padded
              stores holding bit-identical row values to the flat init (the
              flat pool is drawn first, then scattered), so flat and padded
              jobs from the same key are numerically indistinguishable.
    """
    kg = KeyGen(key)
    D = cfg.embed_dim
    # one pooled row array for all tables (rows laid out at cfg.table_offsets)
    params: Dict[str, Any] = {
        "tables": dense_init(kg(), (cfg.total_embedding_rows, D), D,
                             jnp.float32),
    }
    d_in = cfg.n_dense + cfg.n_tables * D
    mlp = {}
    prev = d_in
    for li, h in enumerate(cfg.mlp_dims):
        mlp[f"w{li}"] = dense_init(kg(), (prev, h), prev, jnp.float32)
        mlp[f"b{li}"] = jnp.zeros((h,), jnp.float32)
        prev = h
    mlp["w_out"] = dense_init(kg(), (prev, 1), prev, jnp.float32)
    mlp["b_out"] = jnp.zeros((1,), jnp.float32)
    params["mlp"] = mlp

    if cfg.kind == "wide_deep":
        params["wide"] = jnp.zeros((cfg.total_embedding_rows, 1), jnp.float32)
        params["wide_dense"] = jnp.zeros((cfg.n_dense,), jnp.float32)
    if cfg.kind == "dcn":
        params["cross"] = {
            f"w{li}": dense_init(kg(), (d_in,), d_in, jnp.float32)
            for li in range(cfg.cross_layers)}
        params["cross_b"] = {
            f"b{li}": jnp.zeros((d_in,), jnp.float32)
            for li in range(cfg.cross_layers)}
    if cfg.kind == "xdeepfm":
        cin = {}
        prev_maps = cfg.n_tables
        for li, maps in enumerate(cfg.cin_layers):
            cin[f"w{li}"] = dense_init(
                kg(), (prev_maps, cfg.n_tables, maps), prev_maps * cfg.n_tables,
                jnp.float32)
            prev_maps = maps
        cin["w_out"] = dense_init(kg(), (sum(cfg.cin_layers),), sum(cfg.cin_layers),
                                  jnp.float32)
        params["cin"] = cin
    if layout is not None:
        # pad AFTER drawing every key so flat/padded inits are value-equal
        params["tables"] = layout.pad_rows(params["tables"])
        if "wide" in params:
            params["wide"] = layout.pad_rows(params["wide"])
    return params


def dlrm_param_specs(cfg: DLRMConfig, layout=None) -> Dict[str, Any]:
    """Logical-axis spec tree for ``init_dlrm``'s params.

    Args:
      cfg:    the DLRM workload config.
      layout: optional ``PaddedLayout``; padded pooled stores shard their
              *leading* (n_ps) axis over the PS/model axis — an equal split
              of n_ps shards, i.e. exactly one balanced range per device.
    """
    pooled = ("vocab", None, None) if layout is not None else ("vocab", None)
    specs: Dict[str, Any] = {
        "tables": pooled,               # pooled rows over the PS/model axis
        "mlp": {},
    }
    for li, h in enumerate(cfg.mlp_dims):
        specs["mlp"][f"w{li}"] = (None, None)
        specs["mlp"][f"b{li}"] = (None,)
    specs["mlp"]["w_out"] = (None, None)
    specs["mlp"]["b_out"] = (None,)
    if cfg.kind == "wide_deep":
        specs["wide"] = ("vocab", None, None) if layout is not None \
            else ("vocab", None)
        specs["wide_dense"] = (None,)
    if cfg.kind == "dcn":
        specs["cross"] = {f"w{li}": (None,) for li in range(cfg.cross_layers)}
        specs["cross_b"] = {f"b{li}": (None,) for li in range(cfg.cross_layers)}
    if cfg.kind == "xdeepfm":
        specs["cin"] = {f"w{li}": (None, None, None) for li in range(len(cfg.cin_layers))}
        specs["cin"]["w_out"] = (None,)
    return specs


def _pool2d(store, layout):
    """Padded (n_ps, max_range, ...) store → the engine's flattened view."""
    if layout is None:
        return store
    with scope("emb_relayout"):
        return store.reshape((layout.padded_rows,) + store.shape[2:])


def _resolve_plan(cfg: DLRMConfig, plan, table_hot, layout):
    """One ``EmbeddingPlan`` per forward: the explicit plan wins; otherwise
    the legacy loose kwargs build the config's default plan
    (``table_hot=None`` → ``cfg.table_hot``, matching the old behavior)."""
    if plan is not None:
        return plan
    return cfg.embedding_plan(table_hot=table_hot, layout=layout)


def sparse_param_keys(cfg: DLRMConfig) -> tuple:
    """The pooled (vocab-row) parameter leaves the fused sparse backward +
    row-wise optimizer update handles; everything else is dense."""
    return ("tables", "wide") if cfg.kind == "wide_deep" else ("tables",)


def _bag(pool, sparse, plan):
    """One fused lookup, partitioned like the step that traces it: under a
    mesh the kernel runs per batch shard with the pool replicated."""
    pol = current_policy()
    return ops.fused_embedding_bag(pool, sparse, plan=plan, mesh=pol.mesh,
                                   batch_axes=pol.rules.get("batch", ()))


def dlrm_embeddings(params, batch, cfg: DLRMConfig, plan) -> Dict[str, Any]:
    """Every pooled-store lookup of one forward, in one dict.

    The seam the fused sparse-update training step differentiates at: the
    returned bag outputs are the only consumers of the pooled stores, so
    their cotangents (via ``jax.vjp``) feed ``ops.sparse_row_grads``
    directly instead of materializing dense (R, D) gradients.

    Returns ``{"deep": (B, n_tables, D)}`` plus ``{"wide": (B, n_tables, 1)}``
    for wide_deep.
    """
    embs = {"deep": _bag(_pool2d(params["tables"], plan.layout),
                         batch["sparse"], plan)}
    if cfg.kind == "wide_deep":
        embs["wide"] = _bag(_pool2d(params["wide"], plan.layout),
                            batch["sparse"], plan.with_combiner("sum"))
    return embs


def _field_embeddings(params, batch, cfg: DLRMConfig, table_hot=None,
                      layout=None, plan=None):
    """All per-field embeddings in ONE fused call. -> (B, n_tables, D)."""
    plan = _resolve_plan(cfg, plan, table_hot, layout)
    return _bag(_pool2d(params["tables"], plan.layout), batch["sparse"], plan)


def _deep_mlp(params, x, cfg: DLRMConfig):
    h = x
    for li in range(len(cfg.mlp_dims)):
        h = jax.nn.relu(h @ params["mlp"][f"w{li}"] + params["mlp"][f"b{li}"])
    return (h @ params["mlp"]["w_out"] + params["mlp"]["b_out"])[:, 0]


def dlrm_forward_from_embeddings(params, batch, embs: Dict[str, Any],
                                 cfg: DLRMConfig) -> jnp.ndarray:
    """The dense interaction network given the pooled-store lookups.

    ``embs`` is ``dlrm_embeddings``'s output; no pooled store is read here,
    so differentiating this function w.r.t. ``embs`` (and the dense params)
    is the whole backward minus the sparse scatter — the split the fused
    sparse-update step exploits.
    """
    with scope("dense"):
        emb = constrain(embs["deep"], ("batch", None, None))     # (B, m, D)
        B = emb.shape[0]
        x0 = jnp.concatenate([batch["dense"], emb.reshape(B, -1)], axis=-1)

        if cfg.kind == "wide_deep":
            deep = _deep_mlp(params, x0, cfg)
            wide = batch["dense"] @ params["wide_dense"] + jnp.sum(
                embs["wide"][..., 0], axis=1)
            return deep + wide

        if cfg.kind == "dcn":
            x = x0
            for li in range(cfg.cross_layers):
                w = params["cross"][f"w{li}"]
                b = params["cross_b"][f"b{li}"]
                x = x0 * (x @ w)[:, None] + b + x
            return _deep_mlp(params, x, cfg)

        if cfg.kind == "xdeepfm":
            Xk = emb                                             # (B, H0=m, D)
            feats = []
            for li in range(len(cfg.cin_layers)):
                inter = jnp.einsum("bhd,bmd->bhmd", Xk, emb)
                Xk = jnp.einsum("bhmd,hmn->bnd", inter, params["cin"][f"w{li}"])
                feats.append(jnp.sum(Xk, axis=-1))               # (B, maps)
            cin_out = jnp.concatenate(feats, axis=-1) @ params["cin"]["w_out"]
            return _deep_mlp(params, x0, cfg) + cin_out

        raise ValueError(cfg.kind)


def dlrm_forward(params, batch, cfg: DLRMConfig, table_hot=None,
                 layout=None, plan=None) -> jnp.ndarray:
    """batch: {dense (B,n_dense) f32, sparse (B,m,hot) i32} -> logit (B,).

    ``plan`` (an ``EmbeddingPlan``) carries every static knob of the fused
    embedding engine; the legacy ``table_hot``/``layout`` kwargs build the
    config's default plan (``table_hot=None`` → ``cfg.table_hot``; sparse
    ids stay in the flat space — translation happens inside the engine).
    The forward is ``dlrm_embeddings`` (every pooled-store lookup) composed
    with ``dlrm_forward_from_embeddings`` (the dense interaction network).
    """
    plan = _resolve_plan(cfg, plan, table_hot, layout)
    embs = dlrm_embeddings(params, batch, cfg, plan)
    return dlrm_forward_from_embeddings(params, batch, embs, cfg)


def dlrm_loss_from_embeddings(params, batch, embs: Dict[str, Any],
                              cfg: DLRMConfig) -> jnp.ndarray:
    """BCE-with-logits given precomputed pooled-store lookups."""
    logit = dlrm_forward_from_embeddings(params, batch, embs, cfg)
    y = batch["label"].astype(jnp.float32)
    return jnp.mean(jnp.maximum(logit, 0) - logit * y + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def dlrm_loss(params, batch, cfg: DLRMConfig, table_hot=None,
              layout=None, plan=None) -> jnp.ndarray:
    """Binary cross-entropy with logits on CTR labels.

    ``plan`` (or the legacy ``table_hot``/``layout`` kwargs) is forwarded to
    ``dlrm_forward`` so a live re-plan's measured cache plan and the
    physical padded placement reach the fused engine.
    """
    logit = dlrm_forward(params, batch, cfg, table_hot=table_hot,
                         layout=layout, plan=plan)
    y = batch["label"].astype(jnp.float32)
    return jnp.mean(jnp.maximum(logit, 0) - logit * y + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def dlrm_auc(params, batch, cfg: DLRMConfig, table_hot=None,
             layout=None, plan=None) -> jnp.ndarray:
    """Pairwise AUC estimate on one batch (for Fig 8 convergence tracking)."""
    logit = dlrm_forward(params, batch, cfg, table_hot=table_hot,
                         layout=layout, plan=plan)
    y = batch["label"].astype(jnp.float32)
    pos = y[:, None] > y[None, :]
    gt = (logit[:, None] > logit[None, :]).astype(jnp.float32)
    eq = (logit[:, None] == logit[None, :]).astype(jnp.float32)
    n = jnp.maximum(jnp.sum(pos), 1.0)
    return jnp.sum(pos * (gt + 0.5 * eq)) / n
