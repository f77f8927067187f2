"""Output tokens emitted inside the window (first tokens included) over
the window's length."""


def read(ctx):
    return ctx.window.output_tokens() / ctx.close
