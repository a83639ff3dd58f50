"""What the algorithm needs of the chip for DLRM, from shapes: the floating
point operations of its dense half and the bytes of its table.

Operations, one example: a layer of ``i x o`` weights is ``i o``
multiply-adds forward, as many again for its weights' gradient and as many
for its input's, which the bottom MLP's first layer does not need (its
input is data); the interaction ``T T^t`` of ``v = 1 + fields`` vectors of
``d`` lanes is ``v v d`` forward and twice that backward (``dZ T`` and
``dZ^t T``). Two operations a multiply-add. At the MLPerf sizes (13-512-
256-128, 27 x 128, 479-1024-1024-512-256-1): 2,365,184 multiply-adds in the
MLPs and 93,312 in ``T T^t`` forward, 14,737,664 operations an example
forward and backward, 120.7 GFLOP a microstep of 8,192. The peak they are
held against is the chip's bfloat16 rate; a float32 product at
``precision=highest`` is six bfloat16 passes, so the share cannot pass a
sixth of it (16.7%) while the configuration states float32.

Bytes: plain SGD at ``emb_dim`` lanes, float32, keeps ``w`` alone. A
microstep reads every touched row once for the pull, and the push reads it
again and writes it: three row-widths a touched row, 3 x 512 B at 128
lanes. Rows are those a minibatch really touches (its distinct categorical
rows), not the padded slots of its bucket nor the 13 reserved rows.
"""

from __future__ import annotations

VALUE_BYTES = 4
ROW_PASSES = 3  # read by the pull; read and written by the push
N_DENSE, N_FIELDS = 13, 26


def mlp_macs(sizes: list) -> int:
    """Multiply-adds of one example's forward pass through the layers
    ``sizes[0] -> ... -> sizes[-1]``."""
    return sum(i * o for i, o in zip(sizes, sizes[1:]))


def example_flops(emb_dim: int, bot: list, top: list) -> int:
    """Operations of one example, forward and backward, in the two MLPs
    and the interaction."""
    vectors = 1 + N_FIELDS
    pairs = vectors * (vectors - 1) // 2
    bot_sizes, top_sizes = [N_DENSE, *bot], [emb_dim + pairs, *top]
    macs = 3 * (mlp_macs(bot_sizes) + mlp_macs(top_sizes)) - bot_sizes[0] * bot_sizes[1]
    macs += 3 * vectors * vectors * emb_dim
    return 2 * macs


def step_flops(settings: dict) -> int:
    """One microstep of ``settings['minibatch']`` examples."""
    return int(settings["minibatch"]) * example_flops(
        int(settings["emb_dim"]), list(settings["bot"]), list(settings["top"])
    )


def step_bytes(real_keys: float, emb_dim: int, pushes: int = 1) -> float:
    """One microstep on one chip: one pull of ``real_keys`` rows, and
    ``pushes`` updater steps (per_worker mode applies every data shard's
    push in turn) that each read and write as many."""
    return real_keys * emb_dim * VALUE_BYTES * (1 + 2 * pushes)
