def read(ctx):
    return ctx["compiles_in_window"]
