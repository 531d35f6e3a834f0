"""Bytes one `insert_or_assign` of a new key into a full table needs at
the configuration's published widths, whatever implements it: the key and
its row in, the digest rows of both candidate buckets (to learn the key is
absent), the score rows of both (to find each bucket's lowest score), the
evicted slot's key, digest, score and row written, and the status out.
The packed plane's padding is not counted."""


def bytes_per_key(config: dict) -> float:
    slots = config["slots_per_bucket"]
    key, score = config["key_bytes"], config["score_bytes"]
    emb = 4 * config["dim"]
    row = emb + 4                          # one rowwise-adagrad column
    buckets = config["buckets_per_key"]
    probe = buckets * slots                # digest rows
    min_score = buckets * slots * score    # score rows
    write = key + 1 + score + row
    return key + emb + probe + min_score + write + 1
