"""Plain float32 reference of the first rounds of a FedOptima pod run.

Written from the paper's round (Alg. 1-4) and the published layer
equations, in plain ``jax.numpy``: no vmap, sharding, scan, remat or
kernels, and nothing imported from the program.  Weights and token
streams are made here from the seed, by the same recipe as the program's
initialiser and data feed, so both sides train the same model on the
same rows.
"""
