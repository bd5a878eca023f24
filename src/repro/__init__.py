"""FedGAN reproduction (arXiv:2006.07228) grown toward a production-scale
jax sharded training + serving system."""
