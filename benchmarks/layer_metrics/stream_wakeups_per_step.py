"""Wake-ups of the server's thread a settled step that handed anything over:
the sum of `stream_wakeups` over the window's samples, over the samples with
`stream_items` > 0. Reads 1 since PR 36 (one wake-up a step, whatever the
rows); the parent of PR 36 would have read the rows a step. None where the
samples carry no such fields or no step handed anything over."""


def read(ctx):
    gave = [s for s in ctx.steps or ()
            if s.get("stream_items", 0) > 0 and "stream_wakeups" in s]
    if not gave:
        return None
    return sum(int(s["stream_wakeups"]) for s in gave) / len(gave)
