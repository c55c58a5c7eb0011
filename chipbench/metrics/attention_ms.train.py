"""Device ms per traced train step in ops under the program's ``attention``
scope (``models/attention.apply_attention``): xl_rel attention's
projections, scores, relative shift, softmax and output, in the forward, the
recomputed forward and the backward. Read from each op's own ``tf_op``
(chipbench/scopes.py); None when no op ran under the scope."""
from chipbench import scopes


def read(run, outcome):
    return scopes.layer_ms(run, outcome, "attention")
