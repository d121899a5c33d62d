"""Peak of the fullest chip in 1e9 bytes: `memory_stats()` `peak_bytes_in_use` plus
`peak_bytes_reserved` (arrays plus the loaded programs' temporaries)."""
LAYER = "device"
UNIT = "GB"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def compute(run):
    import common

    return common.hbm_peak_gb(run)
