from repro.ckpt.checkpoint import (CheckpointManager,  # noqa: F401
                                   leaf_name, read_leaves)
