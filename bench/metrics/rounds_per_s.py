"""Federated rounds completed per second over the whole measured window
(host clock; every call ends in a device sync)."""


def read(ctx):
    w = ctx["window"]
    return w["rounds"] / w["seconds"]
