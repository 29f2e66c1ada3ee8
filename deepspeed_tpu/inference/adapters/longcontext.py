"""LongContextAdapter — block-sparse decode past a length threshold.

GPT-2 weights, long-context attention policy: query positions below
``threshold`` use the full causal mask (token-identical to GPT2Adapter —
the parity half of the contract), positions at or above it see only the
fixed local+stride block layout (FixedSparsityConfig, unidirectional)
from ops/sparse_attention/sparsity_config.py. The sparse mask lives in
the einsum attention path of models/generation.py behind the defaulted
``sparse_*`` fields of ``_GenCfg`` — this module never imports
generation directly (ADAPTER rule); it only constructs the spec and
inherits GPT2Adapter's delegating methods.

Composition with the KV hierarchy is config-level, not adapter-level:
host offload (kv_hierarchy) keeps cold slots' planes out of HBM while
the active window decodes block-sparse, which is what lets a session
longer than dense-HBM capacity complete (the capacity pin in
tests/unit/test_adapters.py).

Ring fallback: when the bound mesh carries a 'seq' axis of size > 1,
``bind`` switches to sequence-parallel DENSE attention instead — the KV
pool's plane dimension is sharded over 'seq' (kv_pool.pool_shardings)
and XLA's SPMD partitioner turns the attention contractions into the
ring-style collectives of ops/transformer/ring_attention.py's serving
regime. Sparse masking and sequence sharding compose poorly (every shard
would materialize the full layout), so 'seq' meshes take the ring path.
"""

import dataclasses
from typing import ClassVar

from deepspeed_tpu.inference.adapters.gpt2 import GPT2Adapter
from deepspeed_tpu.parallel import mesh as mesh_lib


@dataclasses.dataclass(frozen=True)
class LongContextAdapter(GPT2Adapter):
    """GPT-2 decode with block-sparse attention above a length threshold.

    ``mode`` is 'block_sparse' (default) or 'ring' (sequence-parallel
    dense — chosen by ``bind`` when the mesh has a 'seq' axis)."""

    mode: str = "block_sparse"
    name: ClassVar[str] = "longcontext"

    @classmethod
    def from_model(cls, model, threshold=4096, block=64, num_local_blocks=4,
                   num_global_blocks=1):
        """Adapter from a GPT-2 model/config. ``threshold`` is the query
        position where attention turns block-sparse; ``block`` /
        ``num_local_blocks`` / ``num_global_blocks`` are the
        FixedSparsityConfig local+stride geometry. Flash decode is forced
        off — the sparse mask needs the einsum path."""
        if threshold <= 0:
            raise ValueError("threshold must be > 0, got {}".format(threshold))
        # Reaches generation.as_gencfg through the parent classmethod —
        # this module itself never imports models.generation (ADAPTER rule).
        gcfg = GPT2Adapter.from_model(model, use_flash_decode=False).gcfg
        return cls(gcfg._replace(sparse_block=int(block),
                                 sparse_num_local=int(num_local_blocks),
                                 sparse_num_global=int(num_global_blocks),
                                 sparse_threshold=int(threshold)))

    @property
    def threshold(self):
        return self.gcfg.sparse_threshold

    def bind(self, config, mesh=None):
        adapter = self
        if mesh is not None and mesh_lib.sp_size(mesh) > 1:
            # Ring fallback: dense attention over a sequence-sharded
            # plane; the sparse mask is dropped (see module docstring).
            adapter = dataclasses.replace(
                adapter, mode="ring",
                gcfg=adapter.gcfg._replace(sparse_threshold=0))
        # Paged cache-spec variant — same stamp as GPT2Adapter.bind:
        # the einsum path gathers the arena back to logical planes
        # before the sparse mask applies, so block-sparse decode and
        # the paged pool compose without a dedicated kernel.
        page_len = (int(getattr(config, "kv_page_len", 0))
                    if config is not None
                    and getattr(config, "paged_kv", False) else 0)
        if page_len != adapter.gcfg.kv_page_len:
            adapter = dataclasses.replace(
                adapter, gcfg=adapter.gcfg._replace(kv_page_len=page_len))
        return adapter

    def observe(self, snap, registry):
        registry.gauge("sparse_decode_threshold").set(
            float(self.gcfg.sparse_threshold))
