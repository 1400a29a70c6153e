"""Bytes a fused SyncTest batch must move through HBM, from its shapes.

The carry (world, snapshot ring, input ring, checksum history) comes in from
HBM once per batch and goes back once; the batch's inputs come in once. In
between, the kernel keeps it in VMEM, so this is the least traffic any
implementation of the batch can have. The ex_game world is 5 int32 words per
entity (pos x/y, vel x/y, rot) plus the frame word. On a mesh the entity
words split over the `entity` axis; each chip moves its share.
"""

WORDS_PER_ENTITY = 5


def synctest_batch_bytes(entities: int, players: int, check_distance: int,
                         batch: int, entity_shards: int = 1) -> int:
    d = check_distance
    world = 4 * (WORDS_PER_ENTITY * entities // entity_shards + 1)
    ring = (d + 2) * world
    input_ring = (d + 2) * players
    history = 3 * 4 * (d + 2) + 4 * 4  # tags, hi, lo; flag, frames
    carry = world + ring + input_ring + history
    return 2 * carry + batch * players
