def read(ctx):
    return ctx["window"]["traffic_compiles"]
