"""Classifier veto walk per call: the program's ``classifier.veto_walk``
spans (ops/service.py ``ServiceClassifier._veto_walk``) summed over the
traced window."""

from lib import spans


def read(run):
    return spans.span_ms(run, ["classifier.veto_walk"])
