"""Wide & Deep CTR (BASELINE.json Criteo-1TB config: 1B-row hashed sparse
table, AdaGrad).

Wide side: sparse linear weights over hashed feature ids (the reference-style
PS table). Deep side: field embeddings concatenated into an MLP — dense
matmuls that land on the MXU in bf16-friendly shapes. One shared table row
per feature carries ``[w, e_0..e_{k-1}]`` (dim = 1 + k) so wide weight and
deep embedding move in one pull/push.

Config: ``embed_dim`` (k), ``hidden_dims`` (list, e.g. "256,128"), plus the
sparse-base keys.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from swiftsnails_tpu.models.registry import register_model
from swiftsnails_tpu.models.sparse_base import SparseCTRTrainer
from swiftsnails_tpu.utils.config import Config


@register_model("widedeep")
class WideDeepTrainer(SparseCTRTrainer):
    name = "widedeep"

    def __init__(self, config: Config, mesh=None, data=None, tracer=None):
        self.k = config.get_int("embed_dim", 16)
        hidden = config.get_str("hidden_dims", "128,64")
        self.hidden_dims: List[int] = [int(x) for x in hidden.replace(";", ",").split(",") if x]
        super().__init__(config, mesh=mesh, data=data, tracer=tracer)

    @property
    def table_dim(self) -> int:
        return 1 + self.k

    def init_dense(self, rng) -> Dict[str, Any]:
        dims = [self.num_fields * self.k] + self.hidden_dims + [1]
        params: Dict[str, Any] = {"bias": jnp.zeros(())}
        keys = jax.random.split(rng, len(dims) - 1)
        for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
            scale = jnp.sqrt(2.0 / d_in)
            params[f"w{i}"] = jax.random.normal(keys[i], (d_in, d_out)) * scale
            params[f"b{i}"] = jnp.zeros((d_out,))
        if self._tp():
            params = self._tp_shard_dense(params)
        return params

    def _tp(self) -> bool:
        """Tensor-parallel deep side (config ``dense_tp: 1``): hidden layers
        alternate column-/row-parallel over the ``model`` axis (Megatron
        pattern) — optional per SURVEY §2.8, the MLP is small enough that DP
        alone is usually right."""
        return self.mesh is not None and self.config.get_bool("dense_tp", False)

    def _tp_shard_dense(self, params: Dict[str, Any]) -> Dict[str, Any]:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from swiftsnails_tpu.parallel.mesh import MODEL_AXIS

        n_layers = len(self.hidden_dims) + 1
        out: Dict[str, Any] = dict(params)
        for i in range(n_layers):
            col = i % 2 == 0  # even layers split columns, odd split rows
            w_spec = P(None, MODEL_AXIS) if col else P(MODEL_AXIS, None)
            b_spec = P(MODEL_AXIS) if col else P(None)
            last = i == n_layers - 1
            if last:  # final projection to 1 unit: keep replicated
                w_spec, b_spec = P(None, None), P(None)
            out[f"w{i}"] = jax.device_put(params[f"w{i}"], NamedSharding(self.mesh, w_spec))
            out[f"b{i}"] = jax.device_put(params[f"b{i}"], NamedSharding(self.mesh, b_spec))
        return out

    def _mlp(self, dense: Dict[str, Any], x: jax.Array) -> jax.Array:
        tp = self._tp()
        if tp:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from swiftsnails_tpu.parallel.mesh import MODEL_AXIS

            cons = lambda v, spec: jax.lax.with_sharding_constraint(
                v, NamedSharding(self.mesh, spec)
            )
        n_layers = len(self.hidden_dims) + 1
        for i in range(n_layers):
            x = x @ dense[f"w{i}"] + dense[f"b{i}"]
            if tp and i < n_layers - 1:
                # activations sharded on the hidden dim after col-parallel
                # layers; XLA inserts the reduce for the row-parallel ones
                spec = P(None, MODEL_AXIS) if i % 2 == 0 else P(None, None)
                x = cons(x, spec)
            if i < n_layers - 1:
                x = jax.nn.relu(x)
        return x[..., 0]

    def forward(self, pulled, dense, mask):
        b, f = mask.shape
        wide = jnp.where(mask, pulled[..., 0], 0).sum(axis=1)
        emb = jnp.where(mask[..., None], pulled[..., 1:], 0)  # [B, F, k]
        deep = self._mlp(dense, emb.reshape(b, f * self.k))
        return dense["bias"] + wide + deep
