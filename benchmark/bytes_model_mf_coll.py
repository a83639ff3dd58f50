"""Bytes a chip must RECEIVE over the interconnect for the step's two
collectives at a range-sharded matrix-factorization table, from shapes and
the keys the minibatches really hold; never the padded slots of a bucket.

On a mesh ``data`` D x ``kv`` KV chip (d, k) runs worker d's batch against
shard k of the table. A microstep's pull gives it the rows of its worker's
keys: those shard k owns it holds, the others it has to be sent, one row of
``rank`` float32 each. A microstep's push applies every worker's gradient
to the rows shard k owns: its own worker's it computed, the other workers'
it has to be sent, one row of ``rank`` float32 and the key's int32 each.
Nothing else need cross a link, so this is a lower bound: the program moves
every slot of the bucket, pads and the other shard's rows included.
"""

from __future__ import annotations

VALUE_BYTES = 4
KEY_BYTES = 4


def recv_bytes(keys_owned, rank: int) -> dict:
    """``keys_owned[d][k]``: distinct keys of worker d's minibatch whose rows
    shard k owns, a microstep (a mean over microsteps will do: the count is
    linear). {"pull", "push"}: bytes a microstep a chip receives at the
    least, the mean over the D x KV chips."""
    workers, shards = len(keys_owned), len(keys_owned[0])
    row = rank * VALUE_BYTES
    pull = push = 0.0
    for d in range(workers):
        for k in range(shards):
            pull += (sum(keys_owned[d]) - keys_owned[d][k]) * row
            push += (sum(keys_owned[w][k] for w in range(workers)) - keys_owned[d][k]) * (row + KEY_BYTES)
    chips = workers * shards
    return {"pull": pull / chips, "push": push / chips}
