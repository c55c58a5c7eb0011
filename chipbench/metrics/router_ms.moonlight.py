"""Device ms per traced train step in ops under the program's ``router``
scope (``core/moe.apply_moe``): router logits, sigmoid scores, the
selection bias, top-k, the gates' renormalization and the held-expert
filter, in the forward, the recomputed forward and the backward
(chipbench/named_scopes.py); None when no op ran under it."""
from chipbench import named_scopes


def read(run, outcome):
    return named_scopes.scope_ms(run, outcome, "router")
