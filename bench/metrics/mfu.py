"""Whole step against the chip's peak: useful model FLOPs of the window
(prompt tokens computed, cached prefixes and padding excluded, and
tokens generated) over the window and the chips' bf16 peak."""
UNIT = "%"


def read(rec):
    sh = rec.shapes
    flops = 0.0
    for s in rec.window_steps():
        flops += sum(sh.prompt_flops(start, n)
                     for start, (_, n) in zip(s.chunk_starts, s.chunks))
        flops += sum(sh.token_flops(c) for c in s.decode_ctx)
    if flops <= 0:
        return None
    return 100.0 * flops / (rec.seconds * sh.chips * rec.peak["bf16_flops"])
