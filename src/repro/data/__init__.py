from repro.data.synthetic import (
    SyntheticClassification,
    TokenStream,
    make_mnist_like,
    make_spambase_like,
    make_token_stream,
    markov_sequences,
)
from repro.data.sharding import (
    compact_stack,
    dirichlet_shard_indices,
    dirichlet_shards,
    iid_shard_indices,
    iid_shards,
    padded_stack,
    pow2_bucket,
    shard_compact_plan,
)

__all__ = [
    "SyntheticClassification",
    "TokenStream",
    "make_mnist_like",
    "make_spambase_like",
    "make_token_stream",
    "markov_sequences",
    "iid_shards",
    "iid_shard_indices",
    "dirichlet_shards",
    "dirichlet_shard_indices",
    "padded_stack",
    "compact_stack",
    "pow2_bucket",
    "shard_compact_plan",
]
