"""From the ``train()`` call to the start of its first dispatch: matrix
loading, ``TpuEngine.__init__``, sketch, bin and upload."""


def read(ctx):
    return ctx["timeline"][0]["start"] - ctx["train_call"]
