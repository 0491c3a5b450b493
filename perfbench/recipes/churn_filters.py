"""The filters churn clients subscribe and unsubscribe: derived from the
topics the traffic aims at the live subscribers, so the aimed share of
the traffic reaches them too."""

from __future__ import annotations

import random


def churn_filters(seed: int, hits: list, pool: int = 512, **_params):
    """``pool`` filters drawn from the seed out of every filter one of
    the hit topics gives: the topic itself, the topic with one level
    replaced by ``+``, or a proper prefix of two levels or more followed
    by ``#`` (MQTT 3.1.1 section 4.7; each matches the topic it came
    from). A first level alone before ``#`` is left out: ``live/#`` would
    take nine tenths of the aimed traffic, a firehose no live subscriber
    of the population holds either."""
    derived = set()
    for topic in hits:
        levels = topic.split("/")
        derived.add(topic)
        for i in range(len(levels)):
            derived.add("/".join(levels[:i] + ["+"] + levels[i + 1:]))
        for i in range(2, len(levels)):
            derived.add("/".join(levels[:i] + ["#"]))
    ordered = sorted(derived)
    return random.Random(seed + 13).sample(ordered, min(pool, len(ordered)))
