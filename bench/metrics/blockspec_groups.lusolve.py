"""WaveProgram compiler: fused groups that read a grid by BlockSpec because
its tile is narrower than the chip's (8, 128) layout tile, summed over the
``utp.build`` spans of set-up.  A program without the counter reads nothing."""

from bench import program_trace


def read(ctx):
    su = program_trace.summary(ctx)["setup"]
    if su is None:
        return None
    return su["spans"].get("utp.build", {}).get("blockspec_groups")
