"""Plain float32 references of what the benchmark runs: the backbones, the
feature discriminator, the adversarial losses, Adam and the federated
sync, in straightforward ``jax.numpy``.  Nothing here imports the program
or takes anything it made; the weights, data and keys come from the
benchmark's own seeded generators.

Each configuration file names its generator module here
(``"reference": "mamba2"`` is ``reference/mamba2.py``).  The other files
a configuration and its cell bring are listed in ``bench/run.py``'s
docstring.
"""
