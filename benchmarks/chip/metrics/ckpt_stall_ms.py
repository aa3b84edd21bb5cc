"""ckpt_stall_ms: host-clock milliseconds the round loop stood still
for each ``FederatedService.save_checkpoint`` in the traced window."""
UNIT = "ms/save"


def read(run: dict):
    saves = run.get("saves_ms") or []
    return sum(saves) / len(saves) if saves else None
