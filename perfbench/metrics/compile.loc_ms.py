"""T_LoC: the program's own compile time (``CompiledProgram.t_loc``) of
the cell's compile during set-up."""


def read(ctx):
    v = ctx.setup.get("t_loc_s")
    return None if v is None else v * 1e3
