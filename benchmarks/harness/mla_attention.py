"""Counts of the latent attention of one mixed serving step -> (FLOPs,
bytes): multi-head latent attention (DeepSeek-V2) over a latent cache,
whatever implements it. The program counts rows, causal (query, key)
pairs and the context its runs read, summed over the latent layers
(flight records `mla_rows_*`, `mla_pairs_*`, `mla_tokens_read`); what
they cost is counted here, with the benchmark, so that no PR that
claims a gain can change the count.

The LEAST work is counted, kind by kind (decode rows, chunk rows), as
the lesser of the two forms a layer can take:

* absorbed: every pair scores a query row of H x (Dc + Dr) against a
  cached latent row and weighs H x Dc of it, 2 x H x (2 Dc + Dr) FLOPs;
  every row is absorbed into the latent space and out of it, 2 x H x Dn
  x Dc + 2 x H x Dc x Dv;
* expanded: every pair is a head's (Dn + Dr) score and Dv value, 2 x H
  x (Dn + Dr + Dv); every context token a run reads is up-projected
  into every head's key and value, 2 x Dc x H x (Dn + Dv).

So a later change that moves some rows to the other form, or work
between the kernel and XLA, leaves the yardstick true.

Bytes: every context token a run reads comes in once as its cached
(Dc + Dr) numbers; the up-projections W_UK and W_UV once a layer; every
row's query (H x (Dn + Dr)) in and output (H x Dv) out."""
from __future__ import annotations


def mla_least_step(rows_decode, rows_chunk, pairs_decode, pairs_chunk,
                   tokens_read, layers, heads, latent, rope, nope, value,
                   cache_bytes=2, act_bytes=2, weight_bytes=2):
    """(FLOPs, bytes) of one step whose counts are summed over `layers`
    latent layers. A decode row's run reads its context, which is its
    pairs; the chunk runs read the rest of `tokens_read`."""
    absorbed_pair = 2 * heads * (2 * latent + rope)
    absorb_row = 2 * heads * latent * (nope + value)
    expanded_pair = 2 * heads * (nope + rope + value)
    expand_token = 2 * latent * heads * (nope + value)
    flops = 0
    for rows, pairs, read in (
            (rows_decode, pairs_decode, pairs_decode),
            (rows_chunk, pairs_chunk, tokens_read - pairs_decode)):
        flops += min(pairs * absorbed_pair + rows * absorb_row,
                     pairs * expanded_pair + read * expand_token)
    rows = rows_decode + rows_chunk
    nbytes = (tokens_read * (latent + rope) * cache_bytes
              + layers * latent * heads * (nope + value) * weight_bytes
              + rows * heads * (nope + rope + value) * act_bytes)
    return flops, nbytes
