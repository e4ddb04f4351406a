"""Peak device memory as the runtime reports it."""


def peak_bytes(device):
    """`peak_bytes_in_use` counts live buffers only; what the runtime set
    aside for the running programs' temporaries it reports apart, as
    `peak_bytes_reserved` (PR 23 read 13 233 192 960 there for a train step
    whose temporaries the compiler states as 13.27 GB, beside 0.9 GB in
    use). The device's peak is their sum. None where nothing is reported."""
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)
