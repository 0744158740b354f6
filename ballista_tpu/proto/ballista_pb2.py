# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: ballista.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x0e\x62\x61llista.proto\x12\x0c\x62\x61llista_tpu\"*\n\x0cKeyValuePair\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t\"S\n\nFieldProto\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x12\n\narrow_type\x18\x02 \x01(\t\x12\x10\n\x08nullable\x18\x03 \x01(\x08\x12\x11\n\tqualifier\x18\x04 \x01(\t\"7\n\x0bSchemaProto\x12(\n\x06\x66ields\x18\x01 \x03(\x0b\x32\x18.ballista_tpu.FieldProto\"\xf2\x05\n\tExprProto\x12+\n\x06\x63olumn\x18\x01 \x01(\x0b\x32\x19.ballista_tpu.ColumnProtoH\x00\x12-\n\x07literal\x18\x02 \x01(\x0b\x32\x1a.ballista_tpu.LiteralProtoH\x00\x12/\n\x06\x62inary\x18\x03 \x01(\x0b\x32\x1d.ballista_tpu.BinaryExprProtoH\x00\x12\'\n\x03not\x18\x04 \x01(\x0b\x32\x18.ballista_tpu.UnaryProtoH\x00\x12,\n\x08negative\x18\x05 \x01(\x0b\x32\x18.ballista_tpu.UnaryProtoH\x00\x12+\n\x07is_null\x18\x06 \x01(\x0b\x32\x18.ballista_tpu.UnaryProtoH\x00\x12/\n\x0bis_not_null\x18\x07 \x01(\x0b\x32\x18.ballista_tpu.UnaryProtoH\x00\x12)\n\x05\x61lias\x18\x08 \x01(\x0b\x32\x18.ballista_tpu.AliasProtoH\x00\x12\'\n\x04\x63\x61st\x18\t \x01(\x0b\x32\x17.ballista_tpu.CastProtoH\x00\x12\'\n\x04like\x18\n \x01(\x0b\x32\x17.ballista_tpu.LikeProtoH\x00\x12,\n\x07in_list\x18\x0b \x01(\x0b\x32\x19.ballista_tpu.InListProtoH\x00\x12-\n\x07\x62\x65tween\x18\x0c \x01(\x0b\x32\x1a.ballista_tpu.BetweenProtoH\x00\x12,\n\tcase_expr\x18\r \x01(\x0b\x32\x17.ballista_tpu.CaseProtoH\x00\x12\x30\n\tscalar_fn\x18\x0e \x01(\x0b\x32\x1b.ballista_tpu.ScalarFnProtoH\x00\x12*\n\x06\x61gg_fn\x18\x0f \x01(\x0b\x32\x18.ballista_tpu.AggFnProtoH\x00\x12\x30\n\twindow_fn\x18\x10 \x01(\x0b\x32\x1b.ballista_tpu.WindowFnProtoH\x00\x42\x0b\n\texpr_type\"\x8c\x02\n\rWindowFnProto\x12\x0c\n\x04\x66unc\x18\x01 \x01(\t\x12%\n\x04\x61rgs\x18\x02 \x03(\x0b\x32\x17.ballista_tpu.ExprProto\x12-\n\x0cpartition_by\x18\x03 \x03(\x0b\x32\x17.ballista_tpu.ExprProto\x12,\n\x08order_by\x18\x04 \x03(\x0b\x32\x1a.ballista_tpu.SortKeyProto\x12\x11\n\thas_frame\x18\x05 \x01(\x08\x12\x17\n\x0fstart_unbounded\x18\x06 \x01(\x08\x12\x13\n\x0b\x66rame_start\x18\x07 \x01(\x03\x12\x15\n\rend_unbounded\x18\x08 \x01(\x08\x12\x11\n\tframe_end\x18\t \x01(\x03\".\n\x0b\x43olumnProto\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x11\n\tqualifier\x18\x02 \x01(\t\"\xce\x01\n\x0cLiteralProto\x12\x10\n\x06\x62ool_v\x18\x01 \x01(\x08H\x00\x12\x0f\n\x05int_v\x18\x02 \x01(\x03H\x00\x12\x11\n\x07\x66loat_v\x18\x03 \x01(\x01H\x00\x12\x12\n\x08string_v\x18\x04 \x01(\tH\x00\x12\x13\n\tdate_days\x18\x05 \x01(\x05H\x00\x12\x10\n\x06null_v\x18\x06 \x01(\x08H\x00\x12/\n\x08interval\x18\x07 \x01(\x0b\x32\x1b.ballista_tpu.IntervalProtoH\x00\x12\x13\n\tdecimal_v\x18\x08 \x01(\tH\x00\x42\x07\n\x05value\"(\n\rIntervalProto\x12\t\n\x01n\x18\x01 \x01(\x03\x12\x0c\n\x04unit\x18\x02 \x01(\t\"l\n\x0f\x42inaryExprProto\x12%\n\x04left\x18\x01 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12\n\n\x02op\x18\x02 \x01(\t\x12&\n\x05right\x18\x03 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\"3\n\nUnaryProto\x12%\n\x04\x65xpr\x18\x01 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\"A\n\nAliasProto\x12%\n\x04\x65xpr\x18\x01 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12\x0c\n\x04name\x18\x02 \x01(\t\"F\n\tCastProto\x12%\n\x04\x65xpr\x18\x01 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12\x12\n\narrow_type\x18\x02 \x01(\t\"T\n\tLikeProto\x12%\n\x04\x65xpr\x18\x01 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12\x0f\n\x07pattern\x18\x02 \x01(\t\x12\x0f\n\x07negated\x18\x03 \x01(\x08\"q\n\x0bInListProto\x12%\n\x04\x65xpr\x18\x01 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12*\n\x06values\x18\x02 \x03(\x0b\x32\x1a.ballista_tpu.LiteralProto\x12\x0f\n\x07negated\x18\x03 \x01(\x08\"\x93\x01\n\x0c\x42\x65tweenProto\x12%\n\x04\x65xpr\x18\x01 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12$\n\x03low\x18\x02 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12%\n\x04high\x18\x03 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12\x0f\n\x07negated\x18\x04 \x01(\x08\"h\n\tCaseProto\x12/\n\x08\x62ranches\x18\x01 \x03(\x0b\x32\x1d.ballista_tpu.CaseBranchProto\x12*\n\telse_expr\x18\x02 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\"_\n\x0f\x43\x61seBranchProto\x12%\n\x04when\x18\x01 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12%\n\x04then\x18\x02 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\"D\n\rScalarFnProto\x12\x0c\n\x04name\x18\x01 \x01(\t\x12%\n\x04\x61rgs\x18\x02 \x03(\x0b\x32\x17.ballista_tpu.ExprProto\"b\n\nAggFnProto\x12\x0c\n\x04\x66unc\x18\x01 \x01(\t\x12$\n\x03\x61rg\x18\x02 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12\x10\n\x08\x64istinct\x18\x03 \x01(\x08\x12\x0e\n\x06no_arg\x18\x04 \x01(\x08\"]\n\x0cSortKeyProto\x12%\n\x04\x65xpr\x18\x01 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12\x11\n\tascending\x18\x02 \x01(\x08\x12\x13\n\x0bnulls_first\x18\x03 \x01(\x08\"\\\n\x0bJoinKeyPair\x12%\n\x04left\x18\x01 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12&\n\x05right\x18\x02 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\"\xb6\x08\n\x10PhysicalPlanNode\x12\x35\n\x0cparquet_scan\x18\x01 \x01(\x0b\x32\x1d.ballista_tpu.ParquetScanNodeH\x00\x12\x33\n\x0bmemory_scan\x18\x02 \x01(\x0b\x32\x1c.ballista_tpu.MemoryScanNodeH\x00\x12*\n\x06\x66ilter\x18\x03 \x01(\x0b\x32\x18.ballista_tpu.FilterNodeH\x00\x12\x32\n\nprojection\x18\x04 \x01(\x0b\x32\x1c.ballista_tpu.ProjectionNodeH\x00\x12\x30\n\taggregate\x18\x05 \x01(\x0b\x32\x1b.ballista_tpu.AggregateNodeH\x00\x12/\n\thash_join\x18\x06 \x01(\x0b\x32\x1a.ballista_tpu.HashJoinNodeH\x00\x12\x31\n\ncross_join\x18\x07 \x01(\x0b\x32\x1b.ballista_tpu.CrossJoinNodeH\x00\x12&\n\x04sort\x18\x08 \x01(\x0b\x32\x16.ballista_tpu.SortNodeH\x00\x12\x37\n\x15sort_preserving_merge\x18\t \x01(\x0b\x32\x16.ballista_tpu.SortNodeH\x00\x12\x43\n\x13\x63oalesce_partitions\x18\n \x01(\x0b\x32$.ballista_tpu.CoalescePartitionsNodeH\x00\x12=\n\x10\x63oalesce_batches\x18\x0b \x01(\x0b\x32!.ballista_tpu.CoalesceBatchesNodeH\x00\x12.\n\x0blocal_limit\x18\x0c \x01(\x0b\x32\x17.ballista_tpu.LimitNodeH\x00\x12/\n\x0cglobal_limit\x18\r \x01(\x0b\x32\x17.ballista_tpu.LimitNodeH\x00\x12\x34\n\x0brepartition\x18\x0e \x01(\x0b\x32\x1d.ballista_tpu.RepartitionNodeH\x00\x12(\n\x05union\x18\x0f \x01(\x0b\x32\x17.ballista_tpu.UnionNodeH\x00\x12(\n\x05\x65mpty\x18\x10 \x01(\x0b\x32\x17.ballista_tpu.EmptyNodeH\x00\x12\x39\n\x0eshuffle_writer\x18\x11 \x01(\x0b\x32\x1f.ballista_tpu.ShuffleWriterNodeH\x00\x12\x39\n\x0eshuffle_reader\x18\x12 \x01(\x0b\x32\x1f.ballista_tpu.ShuffleReaderNodeH\x00\x12\x41\n\x12unresolved_shuffle\x18\x13 \x01(\x0b\x32#.ballista_tpu.UnresolvedShuffleNodeH\x00\x12*\n\x06window\x18\x14 \x01(\x0b\x32\x18.ballista_tpu.WindowNodeH\x00\x42\x0b\n\tplan_type\"I\n\rScanFileProto\x12\x0c\n\x04\x66ile\x18\x01 \x01(\t\x12\x12\n\nrow_groups\x18\x02 \x03(\r\x12\x16\n\x0e\x61ll_row_groups\x18\x03 \x01(\x08\"Z\n\x12ScanPartitionProto\x12*\n\x05\x66iles\x18\x01 \x03(\x0b\x32\x1b.ballista_tpu.ScanFileProto\x12\x18\n\x10memory_partition\x18\x02 \x01(\x05\"\xc4\x01\n\x0fParquetScanNode\x12)\n\x06schema\x18\x01 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\x12\x34\n\npartitions\x18\x02 \x03(\x0b\x32 .ballista_tpu.ScanPartitionProto\x12\x12\n\nprojection\x18\x03 \x03(\t\x12(\n\x07\x66ilters\x18\x04 \x03(\x0b\x32\x17.ballista_tpu.ExprProto\x12\x12\n\ntable_name\x18\x05 \x01(\t\"b\n\x0eMemoryScanNode\x12)\n\x06schema\x18\x01 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\x12\x11\n\tarrow_ipc\x18\x02 \x01(\x0c\x12\x12\n\npartitions\x18\x03 \x01(\r\"g\n\nFilterNode\x12-\n\x05input\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12*\n\tpredicate\x18\x02 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\"\x92\x01\n\x0eProjectionNode\x12-\n\x05input\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12&\n\x05\x65xprs\x18\x02 \x03(\x0b\x32\x17.ballista_tpu.ExprProto\x12)\n\x06schema\x18\x03 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\"a\n\x0c\x41ggDescProto\x12\x0c\n\x04\x66unc\x18\x01 \x01(\t\x12%\n\x04\x65xpr\x18\x02 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12\x0e\n\x06no_arg\x18\x03 \x01(\x08\x12\x0c\n\x04name\x18\x04 \x01(\t\"\xcf\x01\n\rAggregateNode\x12-\n\x05input\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12,\n\x0bgroup_exprs\x18\x02 \x03(\x0b\x32\x17.ballista_tpu.ExprProto\x12(\n\x04\x61ggs\x18\x03 \x03(\x0b\x32\x1a.ballista_tpu.AggDescProto\x12\x0c\n\x04mode\x18\x04 \x01(\t\x12)\n\x06schema\x18\x05 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\"\x98\x02\n\x0cHashJoinNode\x12,\n\x04left\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12-\n\x05right\x18\x02 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12%\n\x02on\x18\x03 \x03(\x0b\x32\x19.ballista_tpu.JoinKeyPair\x12\x11\n\tjoin_type\x18\x04 \x01(\t\x12\'\n\x06\x66ilter\x18\x05 \x01(\x0b\x32\x17.ballista_tpu.ExprProto\x12\x0c\n\x04mode\x18\x06 \x01(\t\x12)\n\x06schema\x18\x07 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\x12\x0f\n\x07\x64ynamic\x18\x08 \x01(\x08\"\x97\x01\n\rCrossJoinNode\x12,\n\x04left\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12-\n\x05right\x18\x02 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12)\n\x06schema\x18\x03 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\"\x95\x01\n\nWindowNode\x12-\n\x05input\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12-\n\x0cwindow_exprs\x18\x02 \x03(\x0b\x32\x17.ballista_tpu.ExprProto\x12)\n\x06schema\x18\x03 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\"r\n\x08SortNode\x12-\n\x05input\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12(\n\x04keys\x18\x02 \x03(\x0b\x32\x1a.ballista_tpu.SortKeyProto\x12\r\n\x05\x66\x65tch\x18\x03 \x01(\x03\"G\n\x16\x43oalescePartitionsNode\x12-\n\x05input\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\"Y\n\x13\x43oalesceBatchesNode\x12-\n\x05input\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12\x13\n\x0btarget_rows\x18\x02 \x01(\r\"W\n\tLimitNode\x12-\n\x05input\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12\r\n\x05\x66\x65tch\x18\x02 \x01(\x03\x12\x0c\n\x04skip\x18\x03 \x01(\r\"\x82\x01\n\x0fRepartitionNode\x12-\n\x05input\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12\x0e\n\x06scheme\x18\x02 \x01(\t\x12\t\n\x01n\x18\x03 \x01(\r\x12%\n\x04keys\x18\x04 \x03(\x0b\x32\x17.ballista_tpu.ExprProto\"f\n\tUnionNode\x12.\n\x06inputs\x18\x01 \x03(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12)\n\x06schema\x18\x02 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\"O\n\tEmptyNode\x12)\n\x06schema\x18\x01 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\x12\x17\n\x0fproduce_one_row\x18\x02 \x01(\x08\"\xbc\x01\n\x11ShuffleWriterNode\x12-\n\x05input\x18\x01 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12\x0e\n\x06job_id\x18\x02 \x01(\t\x12\x10\n\x08stage_id\x18\x03 \x01(\r\x12\x19\n\x11output_partitions\x18\x04 \x01(\r\x12%\n\x04keys\x18\x05 \x03(\x0b\x32\x17.ballista_tpu.ExprProto\x12\x14\n\x0csort_shuffle\x18\x06 \x01(\x08\"O\n\x13PartitionStatsProto\x12\x10\n\x08num_rows\x18\x01 \x01(\x03\x12\x13\n\x0bnum_batches\x18\x02 \x01(\x03\x12\x11\n\tnum_bytes\x18\x03 \x01(\x03\"\xf3\x01\n\x16PartitionLocationProto\x12\x15\n\rmap_partition\x18\x01 \x01(\r\x12\x0e\n\x06job_id\x18\x02 \x01(\t\x12\x10\n\x08stage_id\x18\x03 \x01(\r\x12\x18\n\x10output_partition\x18\x04 \x01(\r\x12\x13\n\x0b\x65xecutor_id\x18\x05 \x01(\t\x12\x0c\n\x04host\x18\x06 \x01(\t\x12\x13\n\x0b\x66light_port\x18\x07 \x01(\r\x12\x0c\n\x04path\x18\x08 \x01(\t\x12\x0e\n\x06layout\x18\t \x01(\t\x12\x30\n\x05stats\x18\n \x01(\x0b\x32!.ballista_tpu.PartitionStatsProto\"\x97\x01\n\x11ShuffleReaderNode\x12)\n\x06schema\x18\x01 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\x12\x44\n\x13partition_locations\x18\x02 \x03(\x0b\x32\'.ballista_tpu.ShufflePartitionLocations\x12\x11\n\tbroadcast\x18\x03 \x01(\x08\"T\n\x19ShufflePartitionLocations\x12\x37\n\tlocations\x18\x01 \x03(\x0b\x32$.ballista_tpu.PartitionLocationProto\"\x82\x01\n\x15UnresolvedShuffleNode\x12\x10\n\x08stage_id\x18\x01 \x01(\r\x12)\n\x06schema\x18\x02 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\x12\x19\n\x11output_partitions\x18\x03 \x01(\r\x12\x11\n\tbroadcast\x18\x04 \x01(\x08\"\xaf\x01\n\x15\x45xecutorMetadataProto\x12\n\n\x02id\x18\x01 \x01(\t\x12\x0c\n\x04host\x18\x02 \x01(\t\x12\x11\n\tgrpc_port\x18\x03 \x01(\r\x12\x13\n\x0b\x66light_port\x18\x04 \x01(\r\x12\x0e\n\x06vcores\x18\x05 \x01(\r\x12\x14\n\x0cwire_version\x18\x06 \x01(\t\x12\x1b\n\x0e\x64\x65vice_ordinal\x18\x07 \x01(\x05H\x00\x88\x01\x01\x42\x11\n\x0f_device_ordinal\"2\n\x13\x45xecutorMetricProto\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\x01\"\xa8\x02\n\x13TaskDefinitionProto\x12\x0f\n\x07task_id\x18\x01 \x01(\r\x12\x0e\n\x06job_id\x18\x02 \x01(\t\x12\x10\n\x08stage_id\x18\x03 \x01(\r\x12\x15\n\rstage_attempt\x18\x04 \x01(\r\x12\x12\n\npartitions\x18\x05 \x03(\r\x12,\n\x04plan\x18\x06 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12\x12\n\nsession_id\x18\x07 \x01(\t\x12)\n\x05props\x18\x08 \x03(\x0b\x32\x1a.ballista_tpu.KeyValuePair\x12\x16\n\x0elaunch_time_ms\x18\t \x01(\x04\x12\x18\n\x10\x64\x65\x61\x64line_seconds\x18\n \x01(\x01\x12\x14\n\x0ctask_attempt\x18\x0b \x01(\r\"\x90\x01\n\x1aShuffleWritePartitionProto\x12\x18\n\x10output_partition\x18\x01 \x01(\r\x12\x0c\n\x04path\x18\x02 \x01(\t\x12\x10\n\x08num_rows\x18\x03 \x01(\x03\x12\x11\n\tnum_bytes\x18\x04 \x01(\x03\x12\x0e\n\x06layout\x18\x05 \x01(\t\x12\x15\n\rmap_partition\x18\x06 \x01(\r\"\xc6\x01\n\x13OperatorMetricProto\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x13\n\x0boutput_rows\x18\x02 \x01(\x03\x12\x12\n\nelapsed_ns\x18\x03 \x01(\x03\x12\r\n\x05\x64\x65pth\x18\x04 \x01(\r\x12;\n\x05\x65xtra\x18\x05 \x03(\x0b\x32,.ballista_tpu.OperatorMetricProto.ExtraEntry\x1a,\n\nExtraEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\x03:\x02\x38\x01\"\xae\x03\n\x0fTaskStatusProto\x12\x0f\n\x07task_id\x18\x01 \x01(\r\x12\x0e\n\x06job_id\x18\x02 \x01(\t\x12\x10\n\x08stage_id\x18\x03 \x01(\r\x12\x15\n\rstage_attempt\x18\x04 \x01(\r\x12\x12\n\npartitions\x18\x05 \x03(\r\x12\x13\n\x0b\x65xecutor_id\x18\x06 \x01(\t\x12\r\n\x05state\x18\x07 \x01(\t\x12\r\n\x05\x65rror\x18\x08 \x01(\t\x12\x12\n\nerror_kind\x18\t \x01(\t\x12\x11\n\tretryable\x18\n \x01(\x08\x12\x44\n\x12shuffle_partitions\x18\x0b \x03(\x0b\x32(.ballista_tpu.ShuffleWritePartitionProto\x12\x32\n\x07metrics\x18\x0c \x03(\x0b\x32!.ballista_tpu.OperatorMetricProto\x12\x15\n\rmap_partition\x18\r \x01(\r\x12 \n\x18\x66\x65tch_failed_executor_id\x18\x0e \x01(\t\x12\x1d\n\x15\x66\x65tch_failed_stage_id\x18\x0f \x01(\r\x12\x11\n\ttimed_out\x18\x10 \x01(\x08\"\xb9\x01\n\x12\x45xecuteQueryParams\x12\r\n\x03sql\x18\x01 \x01(\tH\x00\x12\x37\n\rphysical_plan\x18\x02 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNodeH\x00\x12\x12\n\nsession_id\x18\x03 \x01(\t\x12,\n\x08settings\x18\x04 \x03(\x0b\x32\x1a.ballista_tpu.KeyValuePair\x12\x10\n\x08job_name\x18\x05 \x01(\tB\x07\n\x05query\"8\n\x12\x45xecuteQueryResult\x12\x0e\n\x06job_id\x18\x01 \x01(\t\x12\x12\n\nsession_id\x18\x02 \x01(\t\"$\n\x12GetJobStatusParams\x12\x0e\n\x06job_id\x18\x01 \x01(\t\"\x90\x02\n\x0eJobStatusProto\x12\x0e\n\x06job_id\x18\x01 \x01(\t\x12\x10\n\x08job_name\x18\x02 \x01(\t\x12\r\n\x05state\x18\x03 \x01(\t\x12\r\n\x05\x65rror\x18\x04 \x01(\t\x12\x38\n\npartitions\x18\x05 \x03(\x0b\x32$.ballista_tpu.PartitionLocationProto\x12\x14\n\x0cqueued_at_ms\x18\x06 \x01(\x04\x12\x13\n\x0b\x65nded_at_ms\x18\x07 \x01(\x04\x12)\n\x06schema\x18\x08 \x01(\x0b\x32\x19.ballista_tpu.SchemaProto\x12\x18\n\x10\x63ompleted_stages\x18\t \x01(\r\x12\x14\n\x0ctotal_stages\x18\n \x01(\r\"B\n\x12GetJobStatusResult\x12,\n\x06status\x18\x01 \x01(\x0b\x32\x1c.ballista_tpu.JobStatusProto\"j\n\x16\x45xecuteQueryPushResult\x12\x0e\n\x06job_id\x18\x01 \x01(\t\x12\x12\n\nsession_id\x18\x02 \x01(\t\x12,\n\x06status\x18\x03 \x01(\x0b\x32\x1c.ballista_tpu.JobStatusProto\"O\n\x16RegisterExecutorParams\x12\x35\n\x08metadata\x18\x01 \x01(\x0b\x32#.ballista_tpu.ExecutorMetadataProto\"8\n\x16RegisterExecutorResult\x12\x0f\n\x07success\x18\x01 \x01(\x08\x12\r\n\x05\x65rror\x18\x02 \x01(\t\"\xa1\x01\n\x0fHeartBeatParams\x12\x13\n\x0b\x65xecutor_id\x18\x01 \x01(\t\x12\x32\n\x07metrics\x18\x02 \x03(\x0b\x32!.ballista_tpu.ExecutorMetricProto\x12\x0e\n\x06status\x18\x03 \x01(\t\x12\x35\n\x08metadata\x18\x04 \x01(\x0b\x32#.ballista_tpu.ExecutorMetadataProto\"%\n\x0fHeartBeatResult\x12\x12\n\nreregister\x18\x01 \x01(\x08\"a\n\x16UpdateTaskStatusParams\x12\x13\n\x0b\x65xecutor_id\x18\x01 \x01(\t\x12\x32\n\x0btask_status\x18\x02 \x03(\x0b\x32\x1d.ballista_tpu.TaskStatusProto\")\n\x16UpdateTaskStatusResult\x12\x0f\n\x07success\x18\x01 \x01(\x08\"\xa8\x01\n\x0ePollWorkParams\x12\x35\n\x08metadata\x18\x01 \x01(\x0b\x32#.ballista_tpu.ExecutorMetadataProto\x12\x17\n\x0f\x63\x61n_accept_task\x18\x02 \x01(\x08\x12\x32\n\x0btask_status\x18\x03 \x03(\x0b\x32\x1d.ballista_tpu.TaskStatusProto\x12\x12\n\nfree_slots\x18\x04 \x01(\r\"B\n\x0ePollWorkResult\x12\x30\n\x05tasks\x18\x01 \x03(\x0b\x32!.ballista_tpu.TaskDefinitionProto\"!\n\x0f\x43\x61ncelJobParams\x12\x0e\n\x06job_id\x18\x01 \x01(\t\"$\n\x0f\x43\x61ncelJobResult\x12\x11\n\tcancelled\x18\x01 \x01(\x08\"$\n\x12\x43leanJobDataParams\x12\x0e\n\x06job_id\x18\x01 \x01(\t\"\x14\n\x12\x43leanJobDataResult\"W\n\x13\x43reateSessionParams\x12,\n\x08settings\x18\x01 \x03(\x0b\x32\x1a.ballista_tpu.KeyValuePair\x12\x12\n\nsession_id\x18\x02 \x01(\t\")\n\x13\x43reateSessionResult\x12\x12\n\nsession_id\x18\x01 \x01(\t\")\n\x13RemoveSessionParams\x12\x12\n\nsession_id\x18\x01 \x01(\t\"\x15\n\x13RemoveSessionResult\"%\n\x13GetJobMetricsParams\x12\x0e\n\x06job_id\x18\x01 \x01(\t\"g\n\x11StageMetricsProto\x12\x10\n\x08stage_id\x18\x01 \x01(\r\x12\x32\n\x07metrics\x18\x02 \x03(\x0b\x32!.ballista_tpu.OperatorMetricProto\x12\x0c\n\x04plan\x18\x03 \x01(\t\"F\n\x13GetJobMetricsResult\x12/\n\x06stages\x18\x01 \x03(\x0b\x32\x1f.ballista_tpu.StageMetricsProto\"<\n\x15\x45xecutorStoppedParams\x12\x13\n\x0b\x65xecutor_id\x18\x01 \x01(\t\x12\x0e\n\x06reason\x18\x02 \x01(\t\"\x17\n\x15\x45xecutorStoppedResult\"_\n\x15LaunchMultiTaskParams\x12\x30\n\x05tasks\x18\x01 \x03(\x0b\x32!.ballista_tpu.TaskDefinitionProto\x12\x14\n\x0cscheduler_id\x18\x02 \x01(\t\"(\n\x15LaunchMultiTaskResult\x12\x0f\n\x07success\x18\x01 \x01(\x08\"3\n\x12StopExecutorParams\x12\r\n\x05\x66orce\x18\x01 \x01(\x08\x12\x0e\n\x06reason\x18\x02 \x01(\t\"\x14\n\x12StopExecutorResult\"F\n\x11\x43\x61ncelTasksParams\x12\x31\n\x05tasks\x18\x01 \x03(\x0b\x32\".ballista_tpu.RunningTaskInfoProto\"I\n\x14RunningTaskInfoProto\x12\x0f\n\x07task_id\x18\x01 \x01(\r\x12\x0e\n\x06job_id\x18\x02 \x01(\t\x12\x10\n\x08stage_id\x18\x03 \x01(\r\"&\n\x11\x43\x61ncelTasksResult\x12\x11\n\tcancelled\x18\x01 \x01(\x08\"%\n\x13RemoveJobDataParams\x12\x0e\n\x06job_id\x18\x01 \x01(\t\"\x15\n\x13RemoveJobDataResult\"2\n\x11\x44iagnosticsParams\x12\x0e\n\x06job_id\x18\x01 \x01(\t\x12\r\n\x05\x63lear\x18\x02 \x01(\x08\"!\n\x11\x44iagnosticsResult\x12\x0c\n\x04\x62ody\x18\x01 \x01(\x0c\"+\n\rProfileParams\x12\r\n\x05start\x18\x01 \x01(\x08\x12\x0b\n\x03\x64ir\x18\x02 \x01(\t\"\x1d\n\rProfileResult\x12\x0c\n\x04\x62ody\x18\x01 \x01(\x0c\"\xd8\x01\n\x13\x45xecutionStageProto\x12\x10\n\x08stage_id\x18\x01 \x01(\r\x12\r\n\x05state\x18\x02 \x01(\t\x12\x12\n\npartitions\x18\x03 \x01(\r\x12\x0f\n\x07\x61ttempt\x18\x04 \x01(\r\x12,\n\x04plan\x18\x05 \x01(\x0b\x32\x1e.ballista_tpu.PhysicalPlanNode\x12\x14\n\x0coutput_links\x18\x06 \x03(\r\x12\x37\n\tcompleted\x18\x07 \x03(\x0b\x32$.ballista_tpu.PartitionLocationProto\"\xbc\x01\n\x13\x45xecutionGraphProto\x12\x0e\n\x06job_id\x18\x01 \x01(\t\x12\x10\n\x08job_name\x18\x02 \x01(\t\x12\x12\n\nsession_id\x18\x03 \x01(\t\x12\x0e\n\x06status\x18\x04 \x01(\t\x12\x31\n\x06stages\x18\x05 \x03(\x0b\x32!.ballista_tpu.ExecutionStageProto\x12,\n\x08settings\x18\x06 \x03(\x0b\x32\x1a.ballista_tpu.KeyValuePairb\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'ballista_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _OPERATORMETRICPROTO_EXTRAENTRY._options = None
  _OPERATORMETRICPROTO_EXTRAENTRY._serialized_options = b'8\001'
  _KEYVALUEPAIR._serialized_start=32
  _KEYVALUEPAIR._serialized_end=74
  _FIELDPROTO._serialized_start=76
  _FIELDPROTO._serialized_end=159
  _SCHEMAPROTO._serialized_start=161
  _SCHEMAPROTO._serialized_end=216
  _EXPRPROTO._serialized_start=219
  _EXPRPROTO._serialized_end=973
  _WINDOWFNPROTO._serialized_start=976
  _WINDOWFNPROTO._serialized_end=1244
  _COLUMNPROTO._serialized_start=1246
  _COLUMNPROTO._serialized_end=1292
  _LITERALPROTO._serialized_start=1295
  _LITERALPROTO._serialized_end=1501
  _INTERVALPROTO._serialized_start=1503
  _INTERVALPROTO._serialized_end=1543
  _BINARYEXPRPROTO._serialized_start=1545
  _BINARYEXPRPROTO._serialized_end=1653
  _UNARYPROTO._serialized_start=1655
  _UNARYPROTO._serialized_end=1706
  _ALIASPROTO._serialized_start=1708
  _ALIASPROTO._serialized_end=1773
  _CASTPROTO._serialized_start=1775
  _CASTPROTO._serialized_end=1845
  _LIKEPROTO._serialized_start=1847
  _LIKEPROTO._serialized_end=1931
  _INLISTPROTO._serialized_start=1933
  _INLISTPROTO._serialized_end=2046
  _BETWEENPROTO._serialized_start=2049
  _BETWEENPROTO._serialized_end=2196
  _CASEPROTO._serialized_start=2198
  _CASEPROTO._serialized_end=2302
  _CASEBRANCHPROTO._serialized_start=2304
  _CASEBRANCHPROTO._serialized_end=2399
  _SCALARFNPROTO._serialized_start=2401
  _SCALARFNPROTO._serialized_end=2469
  _AGGFNPROTO._serialized_start=2471
  _AGGFNPROTO._serialized_end=2569
  _SORTKEYPROTO._serialized_start=2571
  _SORTKEYPROTO._serialized_end=2664
  _JOINKEYPAIR._serialized_start=2666
  _JOINKEYPAIR._serialized_end=2758
  _PHYSICALPLANNODE._serialized_start=2761
  _PHYSICALPLANNODE._serialized_end=3839
  _SCANFILEPROTO._serialized_start=3841
  _SCANFILEPROTO._serialized_end=3914
  _SCANPARTITIONPROTO._serialized_start=3916
  _SCANPARTITIONPROTO._serialized_end=4006
  _PARQUETSCANNODE._serialized_start=4009
  _PARQUETSCANNODE._serialized_end=4205
  _MEMORYSCANNODE._serialized_start=4207
  _MEMORYSCANNODE._serialized_end=4305
  _FILTERNODE._serialized_start=4307
  _FILTERNODE._serialized_end=4410
  _PROJECTIONNODE._serialized_start=4413
  _PROJECTIONNODE._serialized_end=4559
  _AGGDESCPROTO._serialized_start=4561
  _AGGDESCPROTO._serialized_end=4658
  _AGGREGATENODE._serialized_start=4661
  _AGGREGATENODE._serialized_end=4868
  _HASHJOINNODE._serialized_start=4871
  _HASHJOINNODE._serialized_end=5151
  _CROSSJOINNODE._serialized_start=5154
  _CROSSJOINNODE._serialized_end=5305
  _WINDOWNODE._serialized_start=5308
  _WINDOWNODE._serialized_end=5457
  _SORTNODE._serialized_start=5459
  _SORTNODE._serialized_end=5573
  _COALESCEPARTITIONSNODE._serialized_start=5575
  _COALESCEPARTITIONSNODE._serialized_end=5646
  _COALESCEBATCHESNODE._serialized_start=5648
  _COALESCEBATCHESNODE._serialized_end=5737
  _LIMITNODE._serialized_start=5739
  _LIMITNODE._serialized_end=5826
  _REPARTITIONNODE._serialized_start=5829
  _REPARTITIONNODE._serialized_end=5959
  _UNIONNODE._serialized_start=5961
  _UNIONNODE._serialized_end=6063
  _EMPTYNODE._serialized_start=6065
  _EMPTYNODE._serialized_end=6144
  _SHUFFLEWRITERNODE._serialized_start=6147
  _SHUFFLEWRITERNODE._serialized_end=6335
  _PARTITIONSTATSPROTO._serialized_start=6337
  _PARTITIONSTATSPROTO._serialized_end=6416
  _PARTITIONLOCATIONPROTO._serialized_start=6419
  _PARTITIONLOCATIONPROTO._serialized_end=6662
  _SHUFFLEREADERNODE._serialized_start=6665
  _SHUFFLEREADERNODE._serialized_end=6816
  _SHUFFLEPARTITIONLOCATIONS._serialized_start=6818
  _SHUFFLEPARTITIONLOCATIONS._serialized_end=6902
  _UNRESOLVEDSHUFFLENODE._serialized_start=6905
  _UNRESOLVEDSHUFFLENODE._serialized_end=7035
  _EXECUTORMETADATAPROTO._serialized_start=7038
  _EXECUTORMETADATAPROTO._serialized_end=7213
  _EXECUTORMETRICPROTO._serialized_start=7215
  _EXECUTORMETRICPROTO._serialized_end=7265
  _TASKDEFINITIONPROTO._serialized_start=7268
  _TASKDEFINITIONPROTO._serialized_end=7564
  _SHUFFLEWRITEPARTITIONPROTO._serialized_start=7567
  _SHUFFLEWRITEPARTITIONPROTO._serialized_end=7711
  _OPERATORMETRICPROTO._serialized_start=7714
  _OPERATORMETRICPROTO._serialized_end=7912
  _OPERATORMETRICPROTO_EXTRAENTRY._serialized_start=7868
  _OPERATORMETRICPROTO_EXTRAENTRY._serialized_end=7912
  _TASKSTATUSPROTO._serialized_start=7915
  _TASKSTATUSPROTO._serialized_end=8345
  _EXECUTEQUERYPARAMS._serialized_start=8348
  _EXECUTEQUERYPARAMS._serialized_end=8533
  _EXECUTEQUERYRESULT._serialized_start=8535
  _EXECUTEQUERYRESULT._serialized_end=8591
  _GETJOBSTATUSPARAMS._serialized_start=8593
  _GETJOBSTATUSPARAMS._serialized_end=8629
  _JOBSTATUSPROTO._serialized_start=8632
  _JOBSTATUSPROTO._serialized_end=8904
  _GETJOBSTATUSRESULT._serialized_start=8906
  _GETJOBSTATUSRESULT._serialized_end=8972
  _EXECUTEQUERYPUSHRESULT._serialized_start=8974
  _EXECUTEQUERYPUSHRESULT._serialized_end=9080
  _REGISTEREXECUTORPARAMS._serialized_start=9082
  _REGISTEREXECUTORPARAMS._serialized_end=9161
  _REGISTEREXECUTORRESULT._serialized_start=9163
  _REGISTEREXECUTORRESULT._serialized_end=9219
  _HEARTBEATPARAMS._serialized_start=9222
  _HEARTBEATPARAMS._serialized_end=9383
  _HEARTBEATRESULT._serialized_start=9385
  _HEARTBEATRESULT._serialized_end=9422
  _UPDATETASKSTATUSPARAMS._serialized_start=9424
  _UPDATETASKSTATUSPARAMS._serialized_end=9521
  _UPDATETASKSTATUSRESULT._serialized_start=9523
  _UPDATETASKSTATUSRESULT._serialized_end=9564
  _POLLWORKPARAMS._serialized_start=9567
  _POLLWORKPARAMS._serialized_end=9735
  _POLLWORKRESULT._serialized_start=9737
  _POLLWORKRESULT._serialized_end=9803
  _CANCELJOBPARAMS._serialized_start=9805
  _CANCELJOBPARAMS._serialized_end=9838
  _CANCELJOBRESULT._serialized_start=9840
  _CANCELJOBRESULT._serialized_end=9876
  _CLEANJOBDATAPARAMS._serialized_start=9878
  _CLEANJOBDATAPARAMS._serialized_end=9914
  _CLEANJOBDATARESULT._serialized_start=9916
  _CLEANJOBDATARESULT._serialized_end=9936
  _CREATESESSIONPARAMS._serialized_start=9938
  _CREATESESSIONPARAMS._serialized_end=10025
  _CREATESESSIONRESULT._serialized_start=10027
  _CREATESESSIONRESULT._serialized_end=10068
  _REMOVESESSIONPARAMS._serialized_start=10070
  _REMOVESESSIONPARAMS._serialized_end=10111
  _REMOVESESSIONRESULT._serialized_start=10113
  _REMOVESESSIONRESULT._serialized_end=10134
  _GETJOBMETRICSPARAMS._serialized_start=10136
  _GETJOBMETRICSPARAMS._serialized_end=10173
  _STAGEMETRICSPROTO._serialized_start=10175
  _STAGEMETRICSPROTO._serialized_end=10278
  _GETJOBMETRICSRESULT._serialized_start=10280
  _GETJOBMETRICSRESULT._serialized_end=10350
  _EXECUTORSTOPPEDPARAMS._serialized_start=10352
  _EXECUTORSTOPPEDPARAMS._serialized_end=10412
  _EXECUTORSTOPPEDRESULT._serialized_start=10414
  _EXECUTORSTOPPEDRESULT._serialized_end=10437
  _LAUNCHMULTITASKPARAMS._serialized_start=10439
  _LAUNCHMULTITASKPARAMS._serialized_end=10534
  _LAUNCHMULTITASKRESULT._serialized_start=10536
  _LAUNCHMULTITASKRESULT._serialized_end=10576
  _STOPEXECUTORPARAMS._serialized_start=10578
  _STOPEXECUTORPARAMS._serialized_end=10629
  _STOPEXECUTORRESULT._serialized_start=10631
  _STOPEXECUTORRESULT._serialized_end=10651
  _CANCELTASKSPARAMS._serialized_start=10653
  _CANCELTASKSPARAMS._serialized_end=10723
  _RUNNINGTASKINFOPROTO._serialized_start=10725
  _RUNNINGTASKINFOPROTO._serialized_end=10798
  _CANCELTASKSRESULT._serialized_start=10800
  _CANCELTASKSRESULT._serialized_end=10838
  _REMOVEJOBDATAPARAMS._serialized_start=10840
  _REMOVEJOBDATAPARAMS._serialized_end=10877
  _REMOVEJOBDATARESULT._serialized_start=10879
  _REMOVEJOBDATARESULT._serialized_end=10900
  _DIAGNOSTICSPARAMS._serialized_start=10902
  _DIAGNOSTICSPARAMS._serialized_end=10952
  _DIAGNOSTICSRESULT._serialized_start=10954
  _DIAGNOSTICSRESULT._serialized_end=10987
  _PROFILEPARAMS._serialized_start=10989
  _PROFILEPARAMS._serialized_end=11032
  _PROFILERESULT._serialized_start=11034
  _PROFILERESULT._serialized_end=11063
  _EXECUTIONSTAGEPROTO._serialized_start=11066
  _EXECUTIONSTAGEPROTO._serialized_end=11282
  _EXECUTIONGRAPHPROTO._serialized_start=11285
  _EXECUTIONGRAPHPROTO._serialized_end=11473
# @@protoc_insertion_point(module_scope)
