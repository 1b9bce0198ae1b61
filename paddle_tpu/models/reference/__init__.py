"""Plain float32 `jax.numpy` references of the models the Program stack
builds: the equations as published, dense attention, no kernels, no
mixed precision, nothing imported from the program.  The tests hold the
Program to them; benchmark/reference/ keeps copies of its own."""
