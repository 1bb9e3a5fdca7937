"""lane_waste_share.sweep: the share of stepped scenario-cycles spent on
scenarios that had already finished.  A batch steps every scenario until
its longest finishes, so over the window's batches it is
sum(B * max cycles - sum cycles) / sum(B * max cycles).  A count from
the returned statistics, not a time."""


def lane_waste(batches):
    stepped = sum(len(b) * max(b) for b in batches if b)
    if stepped <= 0:
        return None
    return sum(len(b) * max(b) - sum(b) for b in batches if b) / stepped


def read(reduced, record):
    return lane_waste(record.get("batch_cycles", []))
