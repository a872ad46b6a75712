"""Device milliseconds a round spends in the lightning indexer of a
learned sparse attention, forward and backward: the indexer's query, key
and head-weight projections with the key's layer norm and the rotary,
the index scores of a block of queries against the keys it sees, the
selection of the keys a query attends (``select_ms`` is that part
alone), and the alignment term that trains the indexer.  Ops under the
program's ``dopt_index`` scope (inside ``dopt_attn``), busiest chip.
Left out where the program has no such scope."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_index") or None
