"""Operations and bytes a call NEEDS, from its shapes — the numerators of
every roofline share and of model FLOP/s utilization.  Kept with the
benchmark so that no PR that claims a gain can change the count.  Pure
Python."""


def model_flops_per_token(z, seq_len):
    """Forward + backward operations one training token requires (no
    recomputation counted): 6 per parameter that a token's matmuls touch
    (the tied head counts once as a matmul; position and token embedding
    lookups are not matmuls), plus causal attention's score and value
    products: 2 matmuls x 2 ops x seq x h per layer forward, halved by
    causality, tripled for forward + backward."""
    h, f, L, V = z["h"], z["f"], z["layers"], z["vocab"]
    matmul_params = L * (4 * h * h + 2 * h * f) + V * h
    attention = L * 3 * (2 * 2 * seq_len * h) / 2
    return 6 * matmul_params + attention


def flash_fwd_bwd_flops(batch, heads, seq_len, head_dim, causal=True):
    """Operations of one layer's flash attention, forward and backward, as
    the algorithm requires them: forward QK^T and PV (2 matmuls), backward
    dV, dP, dQ, dK (4 matmuls), each 2 x S x S x D per head; a causal mask
    needs half.  The backward's recomputation of QK^T (a fifth matmul) is
    recomputed work and is not counted, so a kernel that recomputes cannot
    reach 100%."""
    per_matmul = 2 * batch * heads * seq_len * seq_len * head_dim
    return 6 * per_matmul * (0.5 if causal else 1.0)


def paged_decode_bytes(context_lens, kv_heads, head_dim, bytes_per_value=2):
    """Bytes one layer's paged-decode call must read from HBM: K and V of
    every live position of every live slot (queries, outputs and the page
    table are noise beside them)."""
    return 2 * sum(context_lens) * kv_heads * head_dim * bytes_per_value


def roofline_pct(flops, bytes_, seconds, peaks):
    """Share of the roofline reached: the least time the chip could take
    (the larger of operations / peak FLOP/s and bytes / peak bytes/s) over
    the time taken.  Returns ``(percent, bound)`` naming which peak binds."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = bytes_ / peaks["hbm_bytes_per_s"]
    least = max(t_compute, t_memory)
    return 100.0 * least / seconds, \
        "compute" if t_compute >= t_memory else "memory"
