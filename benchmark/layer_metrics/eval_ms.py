"""Device milliseconds a round spends evaluating inside the round program
(the global model on the test set, every client on its train rows, a
fleet eval under ``lax.cond``, a holdout's per-epoch eval): ops under the
program's ``dopt_eval`` scope, busiest chip."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_eval")
