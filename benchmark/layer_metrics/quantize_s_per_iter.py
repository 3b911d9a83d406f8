"""Layer ``boosting_loop``: device seconds per boosting iteration of
quantized training's discretization — the operations under the
``quantize`` segment of ``grow/setup`` (the two scales' max-reductions, the
counter-based uniforms, the stochastic rounding to int8 levels, the stack
into the histograms' int8 input).  Part of ``grow_state_s_per_iter``.
``None`` where nothing is quantized, or on a program without the segment."""

from . import _segments


def read(trace, facts):
    return _segments.seconds(trace, facts, "quantize")
