"""Device milliseconds a round spends in the multi-token-prediction
module, forward and backward: the norms of the next token's embedding and
of the main model's last hidden state, their joint projection, the
module's layer (its attention and experts count in ``attn_ms`` and
``moe_ms`` too) and its pass through the shared head (in ``head_ms`` too).
Ops under the program's ``dopt_mtp`` scope, busiest chip.  Left out where
the program has no such scope: a model without the module, or a program
from before it."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_mtp") or None
