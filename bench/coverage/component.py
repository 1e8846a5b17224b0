"""A search covers its key's component: Graph500's TEPS count, the
component's input edges with duplicates and self-loops."""


def covered(traffic, edges, ref, key):
    return int(ref.component_edges[ref.components[key]])
