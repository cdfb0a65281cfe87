"""Mean time of srv.install(...) up to block_until_ready on the installed
program, over the window's live installs."""


def read(ctx):
    ins = ctx["installs"]
    if not ins:
        return None
    return 1e3 * sum(x["install_s"] for x in ins) / len(ins), "ms"
