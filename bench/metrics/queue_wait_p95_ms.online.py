"""p95 of the wait from each request's due time to its admission into
a slot (serve/slots.py t_admit), over the requests due before the profiler
starts in a traced run, in ms."""
from bench import readers


def read(run):
    return readers.queue_wait_ms(run, 95)
