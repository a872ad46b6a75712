from dopt.data.datasets import Dataset, load_dataset
from dopt.data.partition import (assign_client_shards, holdout_split,
                                 iid_split, noniid_split,
                                 orphan_shard_adopters, partition,
                                 reassign_shards)
from dopt.data.pipeline import (BatchPlan, eval_batches, make_batch_plan,
                                gather_batches, sharded_eval_batches,
                                stacked_eval_batches)
from dopt.data.prefetch import (PrefetchStager, next_block_rounds,
                                timed_build)

__all__ = [
    "Dataset",
    "load_dataset",
    "holdout_split",
    "iid_split",
    "noniid_split",
    "partition",
    "reassign_shards",
    "assign_client_shards",
    "orphan_shard_adopters",
    "BatchPlan",
    "eval_batches",
    "make_batch_plan",
    "gather_batches",
    "sharded_eval_batches",
    "stacked_eval_batches",
    "PrefetchStager",
    "next_block_rounds",
    "timed_build",
]
