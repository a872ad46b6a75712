"""Device milliseconds a round spends in the latent attention's two
down-projections and their RMS norms, forward and backward: the queries'
``q_lora_rank`` bottleneck and the keys' and values' ``kv_lora_rank`` one
(with the rotary key's 64 columns), normed.  Ops under the program's
``dopt_latent`` scope (inside ``dopt_qkv``, so ``qkv_ms`` holds them too;
a recompute under ``rematted_computation`` counts here and in
``remat_ms`` both), busiest chip.  Left out where the program has no such
scope: a layer without latent attention, or a program from before it."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_latent") or None
