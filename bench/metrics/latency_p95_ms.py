"""p95 over every request due in the window, from its due time to its
logits back at the client, in ms."""
from bench import readers


def read(run):
    return readers.latency_ms(run, 95)
