"""Device ms per traced train step in ops under the program's ``moe`` scope
(``core/moe.apply_moe``): routing, the sort dispatch and its relayout, the
CVMM Pallas kernels and the combine, in the forward, the recomputed forward
and the backward. Read from each op's own ``tf_op`` (chipbench/scopes.py);
None when no op ran under the scope."""
from chipbench import scopes


def read(run, outcome):
    return scopes.layer_ms(run, outcome, "moe")
