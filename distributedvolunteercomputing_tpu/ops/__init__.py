from distributedvolunteercomputing_tpu.ops.attention import rope

__all__ = ["rope"]
