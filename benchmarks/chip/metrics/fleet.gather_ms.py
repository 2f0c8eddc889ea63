"""Mean host time of the fleet driver's `gather` span per round, in ms:
the cohort's shift rows copied out of the host store."""


def read(record, trace):
    del trace
    spans = record["spans"].get("gather")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
