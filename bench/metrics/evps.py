"""Edges-plus-vertices per second (LDBC Graphalytics): the work of every
job of the window, n + m each (m undirected), over the window's wall time
from the first job's start to the last job's end."""


def read(record):
    w = record["window"]
    return w["work"] / w["seconds"]
