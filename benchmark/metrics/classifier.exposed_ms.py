"""Device idle time per call while the classifier's veto walk holds the
host: the window's idle gaps intersected with the program's
``classifier.veto_walk`` spans, mean over devices."""

from lib import spans


def read(run):
    return spans.exposed_ms(run, ["classifier.veto_walk"])
