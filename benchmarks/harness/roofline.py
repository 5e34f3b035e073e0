"""Shapes -> (FLOPs, bytes) for the kernels whose roofline share the
benchmark reports, and the share itself. Kept with the benchmark so
that no PR that claims a gain can change the count."""
from __future__ import annotations


def splash_mha_fwd_bwd(batch, heads, seq, head_dim, dtype_bytes=2,
                       causal=True):
    """Operations and HBM bytes one causal attention layer needs for a
    forward and a backward pass. Recomputation is NOT counted (a
    backward that rebuilds the scores does more than this; that is its
    cost, not its work).

    Forward: S = Q K^T and O = P V, 2 matmuls of 2*S*S*D each per head.
    Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q: 4.
    A causal mask needs half of each.
    Bytes, forward: read Q K V, write O and the fp32 log-sum-exp.
    Bytes, backward: read Q K V O dO and the log-sum-exp, write dQ dK dV.
    """
    n = batch * heads * seq * head_dim          # elements of one tensor
    matmul = 2 * batch * heads * seq * seq * head_dim
    share = 0.5 if causal else 1.0
    flops = (2 + 4) * matmul * share
    lse = batch * heads * seq * 4
    fwd_bytes = 4 * n * dtype_bytes + lse
    bwd_bytes = (5 + 3) * n * dtype_bytes + lse
    return flops, fwd_bytes + bwd_bytes


def roofline(flops, nbytes, seconds, peaks):
    """(share in %, which bound applies): the least time the chip could
    take — the larger of flops / peak FLOP/s and bytes / peak B/s —
    over the time the kernel took. Never clipped: a share over 100
    means the count is too high or the time leaves out work."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "bandwidth"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
