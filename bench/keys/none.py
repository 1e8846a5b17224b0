"""No key: every run is of the whole graph (``source=None``)."""


def draw(traffic, edges, seed):
    return [None] * (traffic["key_pool"] + 1)
