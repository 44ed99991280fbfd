"""The handle of ``steps/register.py``, ``(reg, disp)``, as the caller holds
it: the registered series array resident and complete (``.cache().tojax()``
then ``block_until_ready``, as ``fetches/ready.py`` takes an answer too
large to bring back) with the displacement trace, already on the host,
kept beside it.  The check compares the pair where it lies (the terminal's
``on_device``): the array against the closed form shifted by THESE
displacements, the displacements by their regret."""

ON_DEVICE = True


def take(handle):
    reg, disp = handle
    x = reg.cache().tojax()
    x.block_until_ready()
    return x, disp
