"""Device calls between the window's stamps whose bucket shape the trainer
had not dispatched before (each is a new program: a compile or a cache
fetch on the dispatch path): the count of the program's timer
``trainer.new_shapes`` over the window. Should be 0."""


def read(run):
    t = run["timers"].get("trainer.new_shapes")
    if t is None:
        return None
    return float(t["count"])
