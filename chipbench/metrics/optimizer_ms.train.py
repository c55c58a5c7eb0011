"""Device ms per traced train step in ops under the program's ``optimizer``
scope (``runtime/steps.make_train_step``): global-norm clipping and the
AdamW update of every parameter. Read from each op's own ``tf_op``
(chipbench/scopes.py); None when no op ran under the scope."""
from chipbench import scopes


def read(run, outcome):
    return scopes.layer_ms(run, outcome, "optimizer")
