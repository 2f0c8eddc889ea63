"""Time the window lost to slow rounds, in ms: the sum, over the window's
rounds, of each round's time beyond the window's median round (host
clock). A run without stalls reads the rounds' jitter, a few ms; a round
that waits on the runtime adds all of its wait. None with fewer than two
rounds."""
import statistics


def read(record, trace):
    del trace
    times = record["round_s"]
    if len(times) < 2:
        return None
    median = statistics.median(times)
    return 1e3 * sum(t - median for t in times if t > median)
