"""The 99th percentile of the gaps between consecutive output tokens, in
seconds, over every request due in the window, as ``itl_p99_s`` would be
taken end to end; in a traced run, over the tokens that came before the
trace's recording stopped.  Under FIFO a prefill holds every decoding
request, so these gaps are the prefill stalls.  It is a per-layer reading
and not end to end because it rests on the few longest stalls, and one
stall more or less swings it by a sixth from run to run."""

from bench import work


def read(r):
    gaps = r.layer.get("itl_gaps")
    return work.percentile(gaps, 99) if gaps else None
