"""What the algorithm needs of the chip for DLRM-DCNv2, from shapes: the
floating point operations of its dense half and the bytes of its table.

Operations, one example: a layer of ``i x o`` weights is ``i o``
multiply-adds forward, as many again for its weights' gradient and as many
for its input's, which the bottom MLP's first layer does not need (its
input is data). A low-rank cross layer over ``D = (1 + fields) emb_dim``
values is two such layers, ``D x rank`` and ``rank x D`` (its elementwise
product with ``x_0`` and its sums are not the matrix unit's). Two
operations a multiply-add. At the MLPerf sizes (13-512-256-128; three cross
layers of 3456 x 512 x 3456; 3456-1024-1024-512-256-1): ``3 x (10,616,832 +
5,243,136 + 170,496) - 6,656`` = 48,084,736 multiply-adds an example,
787.8 GFLOP a microstep of 8,192. The peak they are held against is the
chip's bfloat16 rate; a float32 product at ``precision=highest`` is six
bfloat16 passes, so the share cannot pass a sixth of it (16.7%) while the
configuration states float32.

Bytes: AdaGrad at ``emb_dim`` lanes, float32, keeps ``w`` and ``n``. A
microstep reads ``w`` of every touched row once for the pull, and the push
reads ``w`` and ``n`` again and writes both: FIVE row-widths a touched
row, 5 x 512 B at 128 lanes. Rows are those a minibatch really touches (the
distinct rows of its bags), not the padded slots of its bucket, the 13
reserved rows, or the rows of the table that the streamed scatter passes
over.
"""

from __future__ import annotations

VALUE_BYTES = 4
N_DENSE, N_FIELDS = 13, 26


def mlp_macs(sizes: list) -> int:
    """Multiply-adds of one example's forward pass through the layers
    ``sizes[0] -> ... -> sizes[-1]``."""
    return sum(i * o for i, o in zip(sizes, sizes[1:]))


def example_flops(emb_dim: int, bot: list, top: list, cross_layers: int, cross_rank: int) -> int:
    """Operations of one example, forward and backward, in the two MLPs
    and the cross network."""
    width = (1 + N_FIELDS) * emb_dim
    bot_sizes, top_sizes = [N_DENSE, *bot], [width, *top]
    cross = cross_layers * 2 * width * cross_rank
    macs = 3 * (cross + mlp_macs(top_sizes) + mlp_macs(bot_sizes)) - bot_sizes[0] * bot_sizes[1]
    return 2 * macs


def step_flops(settings: dict) -> int:
    """One microstep of ``settings['minibatch']`` examples."""
    return int(settings["minibatch"]) * example_flops(
        int(settings["emb_dim"]), list(settings["bot"]), list(settings["top"]),
        int(settings["cross_layers"]), int(settings["cross_rank"]),
    )


def step_bytes(real_keys: float, emb_dim: int, pushes: int = 1) -> float:
    """One microstep on one chip: one pull of ``real_keys`` rows' ``w``, and
    ``pushes`` updater steps (per_worker mode applies every data shard's
    push in turn) that each read and write ``w`` and ``n`` of as many."""
    return real_keys * emb_dim * VALUE_BYTES * (1 + 4 * pushes)
