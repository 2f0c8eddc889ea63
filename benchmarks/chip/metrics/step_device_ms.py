"""Device time of the train-step program per round, in ms: the compiled
program that takes most of the device's time in the window, summed over
its runs, divided by the rounds measured; on several chips, the slowest."""


def read(record, trace):
    per_chip = []
    for c in trace["chips"]:
        if not c["module_s"]:
            return None
        per_chip.append(max(c["module_s"].values()))
    return 1e3 * max(per_chip) / record["rounds"]
