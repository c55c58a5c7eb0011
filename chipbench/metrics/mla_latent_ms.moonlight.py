"""Device ms per traced train step in ops under the program's
``mla_latent`` scope (``models/attention.mla_qkv``): MLA's latent
down-projection, the latent's RMSNorm, the up-projection to per-head keys
and values and the decoupled RoPE, in the forward, the recomputed forward
and the backward (chipbench/named_scopes.py); None when no op ran under
it."""
from chipbench import named_scopes


def read(run, outcome):
    return named_scopes.scope_ms(run, outcome, "mla_latent")
