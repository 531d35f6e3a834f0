"""Bytes one lookup of a training step needs at the configuration's
published widths, whatever implements it, counted for a key the table
holds (the step's misses, a few per cent under zipf, add their admission
on top and are not counted):
  lookup  the digest row of the bucket probed, the key and score read and
          the score written, the query key in, the value row read and the
          embedding written out;
  update  the bucket's digest row again, the row read and written back
          (embedding and optimizer columns), and the gradient in.
The packed plane's padding and whole key and score rows are not counted."""


def bytes_per_key(config: dict) -> float:
    digest_row = config["slots_per_bucket"]
    key, score = config["key_bytes"], config["score_bytes"]
    emb = 4 * config["dim"]
    row = emb + 4          # one rowwise-adagrad column
    lookup = digest_row + key + 2 * score + key + row + emb
    update = digest_row + 2 * row + emb
    return lookup + update
