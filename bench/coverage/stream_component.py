"""A batch's run covers the live input edges of its source's component
in the graph that batch leaves: Graph500's TEPS count, as
``component`` counts it on a static graph."""


def covered(traffic, edges, ref, key):
    return key.stream.source_component(key.index)[1]
