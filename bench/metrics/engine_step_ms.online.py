"""Mean wall time of the benchmark's span around each slot-engine step
(serve/bcnn_engine.py BCNNEngine.step) in the window, before the profiler
starts in a traced run, in ms."""
from bench import readers


def read(run):
    return readers.step_ms_mean(run)
