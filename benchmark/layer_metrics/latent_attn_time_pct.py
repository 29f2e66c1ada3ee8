"""Device self time under the region ``attn`` of a latent-attention model
(the norm, ``q_proj``, ``kv_proj``, ``rope``, ``absorb``, the
``latent_decode`` kernel, ``o_proj``) over device busy time. None for a
configuration without latent attention or a program without the region."""

from benchmark import scope_reduce


def read(run):
    if not run["cell"].config.get("kv_lora_rank"):
        return None
    return scope_reduce.region_pct(run, "attn")
