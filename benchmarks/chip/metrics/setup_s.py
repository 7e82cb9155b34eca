"""Set-up time: from the start of the process to the start of the
window (importing the program, finding the chips, making the inputs
from the seed, compiling or loading from the compile cache, warming
up).  Host clock."""


def read(run):
    return run.setup_s
