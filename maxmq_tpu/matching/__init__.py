"""Topic matching: CPU reference trie, signature-table compiler, and the
JAX/Pallas batched TPU matcher."""

from .topics import is_dollar, parse_share, split_levels, valid_filter, valid_topic_name
from .trie import SubscriberSet, TopicAliases, TopicIndex, merge_subscription

__all__ = [
    "is_dollar", "parse_share", "split_levels", "valid_filter",
    "valid_topic_name", "SubscriberSet", "TopicAliases", "TopicIndex",
    "merge_subscription",
]
