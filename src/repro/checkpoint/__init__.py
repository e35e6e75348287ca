from .checkpointer import (  # noqa: F401
    latest_step, read_manifest, restore_checkpoint, save_checkpoint,
)
