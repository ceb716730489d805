"""ota_round_roofline (%): the least time the chip needs for the round
kernels' minimum HBM bytes at its peak bandwidth, over the summed device
time of those kernels, over the window's rounds.  The kernels are the
step's Mosaic calls that read or write a packed plane of D parameters,
found in the compiled program by their shapes and matched in the trace by
their instruction names (the kernels' function names appear in neither).

The bytes are the algorithm's, not an implementation's, and only those of
the matched kernels' work: per worker plane of D parameters, pass 1
(energies and superposition) reads θ (bf16, 2 B), λ and h (complex f32,
8 B each): 18 B; pass 2 (dual update) reads the same and writes λ: 26 B.
That is 44 B x W x D.  The D-sized Θ is written once and read once (f32,
8 B x D).  The fading redraw is no kernel of these and is not counted.
"""


def round_kernels(program_text: str, d: int) -> set:
    """HLO names of the step's Mosaic kernels that read or write a packed
    plane of ``d`` parameters: the round's kernels, whatever their
    functions are called."""
    from harness import trace as tr
    return {name for name, line in tr.mosaic_calls(program_text).items()
            if f",{d}]" in line or f"[{d}]" in line}


def plane_bytes(workers: int, d: int) -> float:
    return 44.0 * workers * d


def min_bytes_per_round(workers: int, d: int) -> float:
    return plane_bytes(workers, d) + 8.0 * d


def read(ctx):
    if ctx.trace is None:
        return None
    names = round_kernels(ctx.program_text, ctx.n_params)
    t = ctx.op_time_s(lambda n: n in names)
    if t <= 0.0:
        return None
    need = ctx.rounds * min_bytes_per_round(ctx.traffic["workers"],
                                            ctx.n_params)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / t
