"""Slots routed to this chip's held experts per token and expert layer, mean
over the window's steps, from the expert layers' ``routed_slots`` buffers
(1.25 under even routing: ten a token, 64 of 512 held). ``None`` where the
program keeps no such buffer."""


def read(record):
    w = record["window"]
    routed = w.get("routed_slots")
    if not routed:
        return None
    layers = len(routed[0])
    return sum(map(sum, routed)) / (len(routed) * layers
                                    * w["tokens_per_step"])
