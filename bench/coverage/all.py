"""A run covers every input edge of the graph."""


def covered(traffic, edges, ref, key):
    return edges.m
