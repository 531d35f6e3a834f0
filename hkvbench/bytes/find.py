"""Bytes one `find` of a resident key needs at the configuration's
published widths, whatever implements it: the digest row of the bucket
probed, the hit's key and score, the query key in, and the value row read
and written out.  The packed plane's padding and whole key and score rows
are not counted."""


def bytes_per_key(config: dict) -> float:
    digest_row = config["slots_per_bucket"]          # one byte per slot
    key, score = config["key_bytes"], config["score_bytes"]
    value = 4 * config["dim"]
    return digest_row + key + score + key + 2 * value
