"""Expert layer: the rows of the expert that was sent most over the
mean expert's rows, in the worst layer (1.0 is an even spread), over
all the router's experts, held here or not.  From the program's
``expert_load`` on the pool's batches after the window."""


def read(ctx):
    return ctx["facts"].get("load_imbalance")
