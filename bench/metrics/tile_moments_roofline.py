"""Share of its roofline that the ``tile_moments`` kernel reaches in the
traced window: the least time the chip needs to read every real tile
once at the space counter's input size (float32, 3 channels) from HBM
and write its 9 moments, over the kernel's device time. The kernel's
arithmetic (a few VPU operations per pixel) is far below the compute
bound, so the bound is HBM bandwidth. Padding tiles are not counted as
needed work."""

# the kernel is the custom call inside the capture programs
PATTERN = r"custom.call"
PROGRAMS = ("_frame_program_body", "_frame_program_multi")


def needed_bytes(n_tiles: int, size: int, channels: int = 3) -> float:
    return n_tiles * (size * size * channels * 4 + 3 * channels * 4)


def read(run):
    secs, _ = run["trace"].ops_matching(PATTERN, *PROGRAMS)
    tiles = run["tally"]["tiles"]
    if secs <= 0 or not tiles:
        return None
    size = run["config"]["counters"]["space"]["input_size"]
    least = needed_bytes(tiles, size) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
