"""Share (%) of the prompt tokens of requests due in the window that the
prefix cache served (their KV reused, not prefilled)."""
from bench.readers import finished


def read(run):
    reqs = finished(run)
    prompt = sum(int(r.prompt.shape[-1]) for r in reqs)
    if not prompt:
        return None
    return 100.0 * sum(r.prefix_tokens for r in reqs) / prompt
