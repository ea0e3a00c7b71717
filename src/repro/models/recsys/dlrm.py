"""DLRM (arXiv:1906.00091) — RM-2 shape: 26 sparse + 13 dense features.

JAX has no native EmbeddingBag: lookups are ``jnp.take`` + masked sum over a
fixed-hotness index layout, which IS the system's hot loop at serving
time.  Dot-product
feature interaction (upper triangle) + bottom/top MLPs, BCE loss.

``retrieval_score`` implements the retrieval_cand shape: one user query
scored against N candidate item embeddings as a single batched GEMV —
not a loop.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.models.layers import mlp_apply, mlp_init


@dataclass(frozen=True)
class DLRMConfig:
    name: str
    n_dense: int
    n_sparse: int
    embed_dim: int
    bot_mlp: tuple[int, ...]
    top_mlp: tuple[int, ...]
    vocab_sizes: tuple[int, ...]  # one per sparse field
    hotness: int = 8  # multi-hot lookups per field (RM-2 style)
    compute_dtype: str = "float32"

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def n_interact(self) -> int:
        f = self.n_sparse + 1
        return f * (f - 1) // 2


def init_params(key, cfg: DLRMConfig):
    keys = jax.random.split(key, cfg.n_sparse + 2)
    tables = [
        jax.random.normal(keys[i], (v, cfg.embed_dim), jnp.float32)
        * (1.0 / jnp.sqrt(v).astype(jnp.float32))
        for i, v in enumerate(cfg.vocab_sizes)
    ]
    bot = mlp_init(keys[-2], [cfg.n_dense, *cfg.bot_mlp])
    d_top_in = cfg.n_interact + cfg.bot_mlp[-1]
    top = mlp_init(keys[-1], [d_top_in, *cfg.top_mlp])
    return {"tables": tables, "bot": bot, "top": top}


def embed_bags(tables, sparse_idx: jax.Array, dtype) -> jax.Array:
    """sparse_idx int32 [B, F, K] (−1 padded) -> [B, F, D] summed bags."""
    outs = []
    for f, table in enumerate(tables):
        idx = sparse_idx[:, f, :]  # [B, K]
        rows = jnp.take(table.astype(dtype), jnp.clip(idx, 0, table.shape[0] - 1),
                        axis=0)  # [B, K, D]
        mask = (idx >= 0).astype(dtype)[:, :, None]
        outs.append(jnp.sum(rows * mask, axis=1))
    return jnp.stack(outs, axis=1)  # [B, F, D]


def forward(params, batch, cfg: DLRMConfig):
    dense = batch["dense"].astype(cfg.dtype)  # [B, n_dense]
    sparse = batch["sparse"]  # int32 [B, F, K]
    b = dense.shape[0]
    z_bot = mlp_apply(params["bot"], dense, final_act=True)  # [B, D]
    emb = embed_bags(params["tables"], sparse, cfg.dtype)  # [B, F, D]
    feats = jnp.concatenate([z_bot[:, None, :], emb], axis=1)  # [B, F+1, D]
    inter = jnp.einsum("bfd,bgd->bfg", feats, feats)  # [B, F+1, F+1]
    f = feats.shape[1]
    iu, ju = jnp.triu_indices(f, k=1)
    z_int = inter[:, iu, ju]  # [B, n_interact]
    top_in = jnp.concatenate([z_bot, z_int], axis=-1)
    logit = mlp_apply(params["top"], top_in)  # [B, 1]
    return logit[:, 0]


def loss_fn(params, batch, cfg: DLRMConfig):
    logit = forward(params, batch, cfg).astype(jnp.float32)
    y = batch["labels"].astype(jnp.float32)
    # numerically stable BCE-with-logits
    loss = jnp.maximum(logit, 0) - logit * y + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    return jnp.mean(loss)


def retrieval_score(params, batch, cfg: DLRMConfig):
    """Score 1 query against N candidates: [N] logits via one GEMV.

    batch: dense [1, n_dense], sparse [1, F, K], cand [N, D] (item tower
    embeddings).  Two-tower style: user vector = bottom-MLP output combined
    with the mean sparse embedding, scored by dot product.
    """
    dense = batch["dense"].astype(cfg.dtype)
    sparse = batch["sparse"]
    z_bot = mlp_apply(params["bot"], dense, final_act=True)  # [1, D]
    emb = embed_bags(params["tables"], sparse, cfg.dtype)  # [1, F, D]
    user = z_bot + jnp.mean(emb, axis=1)  # [1, D]
    cand = batch["cand"].astype(cfg.dtype)  # [N, D]
    return (cand @ user[0])  # [N]
