"""Share of the traced slice that the device spends in the two forms of a
dense (indexer-free) latent attention layer: own device time under the
scopes ``attn.mla_dense_chunk`` (decompress + flash under the causal mask)
and ``attn.mla_dense_decode`` (the absorbed lane form over every live row).
The projections around them are part ``attn.proj`` of the program's table
and are not counted.  None on a program without the scopes."""
from benchmark import opsbytes_longcat as ob


def read(run):
    return ob.scope_share_pct(run, ob.DENSE_CHUNK, ob.DENSE_DECODE)
