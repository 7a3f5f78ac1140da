"""Layer: ``parallel.expert``.  The fullest held expert's rows over the mean
of the held experts' rows, in the worst layer of the worst batch of the ring
(``routing.ring_rows``): 1 is an even load.  A count from the program's own
routing code, so it is read off the chip too."""
from benchmarks import routing


def read(run):
    worst = [float((rows.max(axis=1) / rows.mean(axis=1)).max())
             for rows in routing.ring_rows(run) if rows.mean(axis=1).all()]
    if not worst:
        return None
    print("[bench] fullest held expert over the mean, worst layer, a batch: "
          + " ".join(f"{x:.3f}" for x in worst), flush=True)
    return max(worst)
