"""Plain reference implementations: the forward pass of an architecture in
straightforward ``jax.numpy`` and float32, no cache, no kernels, no batching
tricks. Tests hold the program to them (model-configs guide §3)."""
