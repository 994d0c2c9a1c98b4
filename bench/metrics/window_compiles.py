"""Whole-program compiles inside the window (the program's trace counter):
set-up warms every shape, so this should read 0."""


def read(r):
    return r.compiles
