"""Sampled token ids a written frame carried: the rise of
ollamamq_stream_frame_tokens_total over the rise of
ollamamq_stream_frames_total between the window's two scrapes. 1 where ragged
steps feed the streams, up to --decode-steps where fused scans do (PR 36: a
frame is one (step, stream) hand-over). None where the program exports no
such counters (older than PR 36) or wrote no frame."""
from benchmarks.lib import stats

TOKENS = "ollamamq_stream_frame_tokens_total"
FRAMES = "ollamamq_stream_frames_total"


def read(ctx):
    if ctx.prom0 is None or ctx.prom1 is None:
        return None
    ends = [stats.prom_value(p, n) for n in (TOKENS, FRAMES)
            for p in (ctx.prom0, ctx.prom1)]
    if any(v is None for v in ends):
        return None
    tokens, frames = ends[1] - ends[0], ends[3] - ends[2]
    return tokens / frames if frames > 0 else None
