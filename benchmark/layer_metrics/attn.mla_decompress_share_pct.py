"""Share of the traced slice that the device spends decompressing a
slot's latent lane for a chunk's attention (``models/latent_attention.py``
``_attend``, scope ``attn.mla_decompress``: ``c_kv W_kvb`` into every
head's keys and values, and the up-projection's reshape): part
``attn.mla_decompress`` of the slot programs.  The decode steps attend the
latent rows directly (the absorbed form) and decompress nothing.  None on
a program without the join."""
from benchmark import scopes


def read(run):
    return scopes.part_share_pct(run, scopes.SERVE, "attn.mla_decompress")
