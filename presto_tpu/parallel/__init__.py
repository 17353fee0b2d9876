from presto_tpu.parallel.mesh import make_mesh

__all__ = ["make_mesh"]
